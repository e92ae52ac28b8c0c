"""Occupied slot-time over all slot-time of a serve window, in %.

A request holds a slot from its admission to its result (the serving
tier's ``t_admit`` and ``t_done`` stamps); a slot is admitted again as
soon as its request retires, before the deferred finalize stamps the
old result, so the count of requests in that span is capped at the
slots at every instant.  Integrated from the window start to the last
result, over slots x that time."""


def read(r):
    res = r.result
    reqs = getattr(res, "requests", None)
    if not reqs or res.t_end <= res.t_begin:
        return None
    edges = []
    for q in reqs:
        if q.t_admit is None or q.t_done is None:
            continue
        edges.append((max(q.t_admit, res.t_begin), 1))
        edges.append((min(q.t_done, res.t_end), -1))
    edges.sort()
    held, active, last = 0.0, 0, res.t_begin
    for t, step in edges:
        held += min(active, res.slots) * (t - last)
        active += step
        last = t
    return 100.0 * held / (res.slots * (res.t_end - res.t_begin))
