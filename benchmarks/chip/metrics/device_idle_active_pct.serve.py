"""Device idle share of a serve cell's traced window while work waits,
in %: the idle gaps (``trace.py``) whose label is not ``serving.idle``
(the scheduler's sleep with no slot busy and no arrival due), over the
window.  The idle the host causes, with the traffic's own left out."""

IDLE = "serving.idle"


def read(r):
    if getattr(r.result, "requests", None) is None:
        return None
    busy_idle = sum(s for label, s in r.trace.gaps if label != IDLE)
    return 100.0 * busy_idle / r.trace.window_s
