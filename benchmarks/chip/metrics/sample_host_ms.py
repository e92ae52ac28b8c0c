"""Host milliseconds one decode step spends in the program's
``sample_tokens`` call (mean duration of its ``tokens.sample`` spans:
the dispatch of the step's one compiled program; telemetry on in the
traced run only)."""

SPAN = "tokens.sample"


def read(r):
    durs = [e.dur_us for e in getattr(r, "spans", None) or ()
            if e.kind == "span" and e.name == SPAN]
    return 1e-3 * sum(durs) / len(durs) if durs else None
