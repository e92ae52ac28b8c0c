"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device metrics.

The traced run wraps each call it makes into the program in a
``jax.profiler.TraceAnnotation`` (``window``, ``submit``, ``block``,
``serve``, ``calibrate``), so host and device events share the
profiler's clock.  From the file this module gives:

* busy time: the union of the intervals in which an operation ran on
  each device, inside the ``window`` annotation, averaged over devices;
* self time by device operation (an operation that encloses others on
  its line, such as a while loop, keeps only the time its children do
  not cover), named by the HLO instruction;
* launches: executions of a compiled program on the device;
* idle gaps inside the window, longest first, each labelled by the
  innermost benchmark annotation open at the gap's midpoint.

Planes are read with ``jax.profiler.ProfileData``; a device plane is
one whose name starts with ``/device:`` and that has an operations
line.
"""

from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINES = ("XLA Ops",)
LAUNCH_LINES = ("XLA Modules",)
LABELS = ("calibrate", "submit", "block", "serve", "reference", "window")
WINDOW = "window"


@dataclasses.dataclass
class Summary:
    window_s: float                 # length of the traced window
    busy_s: float                   # device busy time, mean over devices
    n_devices: int
    ops: dict                       # op name -> seconds (summed over devices)
    launches: int                   # program executions in the window
    gaps: list                      # [(label, seconds)], longest first

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_annotations(planes):
    """[(name, start_ns, end_ns)] of the benchmark's own annotations."""
    found = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in LABELS:
                    found.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return found


def _short(name: str) -> str:
    """``%fusion.53 = f32[...] fusion(...)`` -> ``fusion.53``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _self_times(events, w0: int, w1: int, ops: dict) -> None:
    """Add each event's self time, clipped to [w0, w1), to ``ops``."""
    stack = []   # [name, start, end, child time]

    def close(frame):
        name, s, e, child = frame
        own = max(0, min(e, w1) - max(s, w0)) - child
        ops[name] = ops.get(name, 0.0) + max(own, 0) * 1e-9

    for ev in sorted(events, key=lambda ev: (ev.start_ns, -ev.duration_ns)):
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += max(0, min(e, w1) - max(s, w0))
        stack.append([_short(ev.name), s, e, 0])
    while stack:
        close(stack.pop())


def _label_at(annotations, t: float) -> str:
    best = None
    for name, s, e in annotations:
        if name != WINDOW and s <= t <= e and (
            best is None or e - s < best[2] - best[1]
        ):
            best = (name, s, e)
    return best[0] if best else "host"


def _reduce_window(planes, notes, w0: int, w1: int) -> Summary:
    busy_total, n_dev, launches = 0.0, 0, 0
    ops: dict = {}
    first_busy = None
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        op_line = next((lines[n] for n in OPS_LINES if n in lines), None)
        if op_line is None:
            continue
        n_dev += 1
        events = [ev for ev in op_line.events
                  if ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0]
        spans = [(max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
                 for ev in events]
        _self_times(events, w0, w1, ops)
        merged = _merge(spans)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        if first_busy is None:
            first_busy = merged
        for n in LAUNCH_LINES:
            if n in lines:
                launches += sum(
                    1 for ev in lines[n].events if w0 <= ev.start_ns < w1
                )
    if n_dev == 0:
        raise ValueError("no device operations line in the trace")
    gaps = []
    edges = [w0] + [x for s, e in first_busy for x in (s, e)] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((_label_at(notes, (a + b) / 2), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n_dev,
        n_devices=n_dev, ops=ops, launches=launches, gaps=gaps,
    )


def reduce(path: str, windows: tuple = (WINDOW,), host_spans=(),
           anchor_s: float | None = None) -> dict:
    """Device metrics over each named annotation of one trace (its first
    occurrence): {label: Summary}.

    ``host_spans`` are the program's own spans, (name, start, end) in
    seconds of the host's ``perf_counter``; ``anchor_s`` is that clock
    when the ``window`` annotation opened.  They join the labels of the
    idle gaps, placed on the trace's clock by that anchor."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    notes = _host_annotations(planes)
    if host_spans and anchor_s is not None:
        start = min(s for n, s, e in notes if n == WINDOW)
        off = start - anchor_s * 1e9
        notes += [(n, s * 1e9 + off, e * 1e9 + off) for n, s, e in host_spans]
    out = {}
    for label in windows:
        spans = [(s, e) for n, s, e in notes if n == label]
        if not spans:
            raise ValueError(f"no {label!r} annotation in {path}")
        out[label] = _reduce_window(planes, notes, *spans[0])
    return out


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line: the device ops
    that took most time, and the longest idle gaps by what the host
    was doing (gaps of one label summed)."""
    by_label: dict = {}
    for label, sec in summary.gaps:
        by_label[label] = by_label.get(label, 0.0) + sec
    ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}
