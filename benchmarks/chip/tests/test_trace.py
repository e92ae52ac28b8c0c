"""The trace reduction, on hand-made events and on a recorded trace.

``data/small.xplane.pb`` is a ``jax.profiler`` trace recorded on one
TPU v5e: three calls of the calibration kernel under ``calibrate``,
then two small Gibbs jobs under ``window`` (``submit`` and ``block``
around each)."""

import types
from pathlib import Path

import pytest

import trace

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name,
        lines=[types.SimpleNamespace(name=k, events=v)
               for k, v in lines.items()])


def test_busy_gaps_and_launches_on_made_events():
    host = _plane("/host:CPU", {"python": [
        _ev("window", 0, 1000), _ev("submit", 0, 100), _ev("block", 100, 800),
    ]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [_ev("fusion.1", 150, 300), _ev("fusion.2", 400, 100),
                    _ev("copy", 900, 200)],
        "XLA Modules": [_ev("jit_step", 140, 400), _ev("jit_w", 900, 200)],
    })
    notes = trace._host_annotations([host, dev])
    s = trace._reduce_window([host, dev], notes, 0, 1000)
    # busy: [150, 500) and [900, 1000) clipped to the window
    assert s.busy_s == pytest.approx(450e-9)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.idle_pct == pytest.approx(55.0)
    assert s.launches == 2
    assert s.ops["copy"] == pytest.approx(100e-9)
    # gaps [0,150) mid 75 in submit; [500,900) mid 700 in block
    assert s.gaps == [("block", pytest.approx(400e-9)),
                      ("submit", pytest.approx(150e-9))]
    b = trace.breakdown(s)
    assert b["device_ops"][0][0] == "fusion.1"
    assert [g[0] for g in b["idle_gaps"]] == ["block", "submit"]


def test_recorded_trace():
    sums = trace.reduce(str(DATA), windows=("calibrate", "window"))
    cal, win = sums["calibrate"], sums["window"]
    assert win.n_devices == 1
    assert 0 < win.busy_s <= win.window_s
    assert 0 <= win.idle_pct < 100
    assert win.launches >= 2
    assert sum(win.ops.values()) >= win.busy_s * (1 - 1e-9)
    assert {g[0] for g in win.gaps} <= {"submit", "block", "host"}
    import calibrate

    assert any(calibrate.KERNEL_NAME in n for n in cal.ops)
    assert not any(calibrate.KERNEL_NAME in n for n in win.ops)
