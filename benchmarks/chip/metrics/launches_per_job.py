"""Programs the device executed per job in a batch window: launches
from the trace over the jobs inside the profiler's window."""


def read(r):
    jobs = getattr(r.result, "jobs", None)
    if not jobs:
        return None
    return r.trace.launches / r.result.traced_jobs
