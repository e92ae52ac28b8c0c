"""Host milliseconds one chunk of the engine's eager kernel loop takes
(mean duration of the program's ``engine.chunk`` spans: operand draw,
kernel call and the kept-row write)."""

SPAN = "engine.chunk"


def read(r):
    durs = [e.dur_us for e in getattr(r, "spans", None) or ()
            if e.kind == "span" and e.name == SPAN]
    return 1e-3 * sum(durs) / len(durs) if durs else None
