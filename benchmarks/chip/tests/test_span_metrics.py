"""The per-layer metrics that read the program's spans and the trace's
labelled idle gaps, on made spans and gaps."""

import types
from pathlib import Path

import pytest

import lib

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def _metric(name):
    return lib.load_module(METRICS / f"{name}.py")


def _span(name, ts_us, dur_us):
    return types.SimpleNamespace(kind="span", name=name, ts_us=ts_us,
                                 dur_us=dur_us)


def _run(spans=(), gaps=(), window_s=10.0, serve=True):
    result = (types.SimpleNamespace(requests=[], wall_s=window_s) if serve
              else types.SimpleNamespace(jobs=[{}]))
    return types.SimpleNamespace(
        result=result, spans=list(spans),
        trace=types.SimpleNamespace(gaps=list(gaps), window_s=window_s))


def test_device_idle_active_leaves_out_the_traffics_idle():
    m = _metric("device_idle_active_pct.serve")
    r = _run(gaps=[("serving.idle", 2.0), ("serving.segment", 0.5),
                   ("serving.admit", 0.25), ("serve", 0.25)])
    assert m.read(r) == pytest.approx(10.0)
    assert m.read(_run(gaps=[("serving.idle", 3.0)])) == 0.0
    assert m.read(_run(serve=False)) is None


@pytest.mark.parametrize("name,span", [
    ("admit_host_ms", "serving.admit"),
    ("segment_dispatch_ms", "serving.dispatch"),
    ("chunk_host_ms", "engine.chunk"),
])
def test_mean_span_duration_in_ms(name, span):
    m = _metric(name)
    r = _run(spans=[_span(span, 0.0, 1000.0), _span(span, 5e3, 3000.0),
                    _span("other", 0.0, 9e6)])
    assert m.read(r) == pytest.approx(2.0)
    # a program without the span (the parent of this metric) reads None
    assert m.read(_run(spans=[_span("other", 0.0, 10.0)])) is None
    assert m.read(types.SimpleNamespace(result=None)) is None


def test_draw_host_pct():
    m = _metric("draw_host_pct")
    r = _run(serve=False, spans=[
        _span("engine.submit", 0.0, 1000.0),
        _span("randomness.draw", 10.0, 100.0),
        _span("randomness.draw", 300.0, 150.0),
        _span("engine.submit", 2000.0, 1000.0),
        _span("randomness.draw", 2010.0, 250.0),
    ])
    assert m.read(r) == pytest.approx(25.0)
    fused = _run(serve=False, spans=[_span("engine.submit", 0.0, 10.0)])
    assert m.read(fused) is None
