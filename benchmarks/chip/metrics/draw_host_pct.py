"""Host time drawing randomness operands, over host time in the engine's
submit, in %: the summed ``randomness.draw`` spans over the summed
``engine.submit`` spans.  None where nothing is drawn on the host
(fused randomness draws in the kernel)."""


def read(r):
    total = {"randomness.draw": 0.0, "engine.submit": 0.0}
    for e in getattr(r, "spans", None) or ():
        if e.kind == "span" and e.name in total:
            total[e.name] += e.dur_us
    if not total["randomness.draw"] or not total["engine.submit"]:
        return None
    return 100.0 * total["randomness.draw"] / total["engine.submit"]
