"""Host milliseconds a serve segment spends dispatching the packed
advance program (mean duration of the program's ``serving.dispatch``
spans): near zero while dispatch is asynchronous, long where it blocks."""

SPAN = "serving.dispatch"


def read(r):
    durs = [e.dur_us for e in getattr(r, "spans", None) or ()
            if e.kind == "span" and e.name == SPAN]
    return 1e-3 * sum(durs) / len(durs) if durs else None
