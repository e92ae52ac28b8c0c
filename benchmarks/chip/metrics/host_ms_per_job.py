"""Host milliseconds inside the engine's submit before it returns, per
job (mean over the window's jobs, host clock)."""


def read(r):
    jobs = getattr(r.result, "jobs", None)
    if not jobs:
        return None
    return 1e3 * sum(j["t_return"] - j["t_submit"] for j in jobs) / len(jobs)
