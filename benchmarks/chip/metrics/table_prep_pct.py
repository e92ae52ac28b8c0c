"""Device time preparing the logits table of each decode step, over busy
time, in %.

The step's ``tokens.table`` scope divides the (B, V) logits by the
temperature and takes the row argmax; the window lookup reads the
table in place, viewed as (B/8, W, 8, 128) tiles of 128-entry windows.
The profiler names device operations by HLO instruction and not by
scope, so the reader takes the operations of the traced window whose
instruction reads or writes a whole table, ``[B,V]`` or
``[B/8,W,8,128]``, other than the vocabulary kernel (which reads single
windows of it): the scaling, the argmax, any copy of the table into
another layout, and the final log-prob gather.  It reads the trace file
itself, since the run's summary keeps only short operation names."""

from pathlib import Path

import trace as trace_mod

TRACES = Path(__file__).resolve().parents[1] / ".traces"
KERNEL = "mh_vocab_window"
WINDOW = 128


def _table_seconds(path: str, shapes: tuple) -> float:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    notes = [n for n in trace_mod._host_annotations(planes)
             if n[0] == trace_mod.WINDOW]
    if not notes:
        return 0.0
    _, w0, w1 = notes[0]
    total, devices = 0.0, 0
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        line = next((ln for ln in plane.lines
                     if ln.name in trace_mod.OPS_LINES), None)
        if line is None:
            continue
        devices += 1
        for ev in line.events:
            s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
            if e > s and KERNEL not in ev.name and any(
                    x in ev.name for x in shapes):
                total += (e - s) * 1e-9
    return total / devices if devices else 0.0


def read(r):
    cfg = r.cfg
    if cfg.get("workload") != "tokens" or r.trace.busy_s <= 0:
        return None
    b, v = int(cfg["batch"]), int(cfg["vocab"])
    shapes = (f"[{b},{v}]", f"[{-(-b // 8)},{-(-v // WINDOW)},8,{WINDOW}]")
    try:
        path = trace_mod.find_xplane(str(TRACES))
    except FileNotFoundError:
        return None
    seconds = _table_seconds(path, shapes)
    return 100.0 * seconds / r.trace.busy_s if seconds else None
