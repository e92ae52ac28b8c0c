"""Device idle share over the traced window of a serve cell, in %:
1 - (union of the intervals in which an operation ran on the device)
over the window (``trace.py``)."""


def read(r):
    if getattr(r.result, "requests", None) is None:
        return None
    return r.trace.idle_pct
