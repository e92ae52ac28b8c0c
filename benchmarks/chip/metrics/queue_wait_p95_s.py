"""95th percentile of the requests' queue wait (due time to slot
admission), from the serving tier's own ``ServeRequest`` stamps."""

import math

import lib


def read(r):
    reqs = getattr(r.result, "requests", None)
    if not reqs:
        return None
    waits = [q.wait_s if q.wait_s is not None else math.inf for q in reqs]
    return lib.percentile(waits, 95)
