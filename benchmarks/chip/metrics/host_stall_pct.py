"""Host time the serving tier spent finalizing retired requests, or
blocked on the dispatch pipeline, over the serve window, in %: the
union of its ``serving.finalize`` and ``serving.pipeline_stall`` spans
(telemetry on in the traced run only) over the host seconds inside
``Scheduler.serve``."""

SPANS = ("serving.finalize", "serving.pipeline_stall")


def read(r):
    res = r.result
    if getattr(res, "requests", None) is None or not getattr(r, "spans", None):
        return None
    spans = sorted((e.ts_us, e.ts_us + e.dur_us) for e in r.spans
                   if e.kind == "span" and e.name in SPANS)
    total, end = 0.0, None
    for s, e in spans:
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return 100.0 * total * 1e-6 / res.wall_s
