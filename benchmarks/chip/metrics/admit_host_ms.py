"""Host milliseconds a serve cell spends admitting one request (mean
duration of the program's ``serving.admit`` spans: the request's init
and its slot writes; telemetry on in the traced run only)."""

SPAN = "serving.admit"


def read(r):
    durs = [e.dur_us for e in getattr(r, "spans", None) or ()
            if e.kind == "span" and e.name == SPAN]
    return 1e-3 * sum(durs) / len(durs) if durs else None
