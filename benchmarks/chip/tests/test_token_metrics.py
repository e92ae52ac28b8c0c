"""The per-layer metrics of the token-sampling decode cell, on made
spans, summaries and results."""

import types
from pathlib import Path

import pytest

import lib

METRICS = Path(__file__).resolve().parents[1] / "metrics"
CFG = {"workload": "tokens", "update": "mh", "batch": 256, "vocab": 129280}


def _metric(name):
    return lib.load_module(METRICS / f"{name}.py")


def _span(name, ts_us, dur_us):
    return types.SimpleNamespace(kind="span", name=name, ts_us=ts_us,
                                 dur_us=dur_us)


def _run(ops=None, busy_s=2.0, window_s=4.0, cfg=CFG, **result):
    res = dict(jobs=[{}], traced_jobs=10, job_blocks=4_000_000,
               job_bytes=132_000_000, kernel_blocks=4_000_000,
               kernel_bytes=70_000)
    res.update(result)
    return types.SimpleNamespace(
        cfg=cfg, result=types.SimpleNamespace(**res), log=lambda m: None,
        trace=types.SimpleNamespace(ops=ops or {}, busy_s=busy_s,
                                    window_s=window_s,
                                    idle_pct=100.0 * (1 - busy_s / window_s)),
        calib_blocks_per_s=4e10, peak={"hbm_bytes_per_s": 8e11})


def test_sample_host_ms_is_the_mean_span():
    m = _metric("sample_host_ms")
    r = _run()
    r.spans = [_span("tokens.sample", 0.0, 200.0),
               _span("tokens.sample", 1e3, 400.0),
               _span("engine.submit", 0.0, 9e6)]
    assert m.read(r) == pytest.approx(0.3)
    r.spans = [_span("engine.submit", 0.0, 10.0)]
    assert m.read(r) is None


def test_device_idle_pct_mh_reads_the_decode_cell():
    m = _metric("device_idle_pct.mh")
    assert m.read(_run(busy_s=3.0, window_s=4.0)) == pytest.approx(25.0)
    assert m.read(_run(jobs=None)) is None


def test_launches_and_host_ms_per_job_read_the_decode_cell():
    jobs = [{"t_submit": 0.0, "t_return": 0.001},
            {"t_submit": 1.0, "t_return": 1.003}]
    r = _run(jobs=jobs, traced_jobs=2)
    r.trace.launches = 6
    assert _metric("launches_per_job").read(r) == pytest.approx(3.0)
    assert _metric("host_ms_per_job").read(r) == pytest.approx(2.0)


def test_vocab_mh_roofline_reads_the_named_kernel():
    m = _metric("vocab_mh_roofline")
    # 10 jobs x 4e6 blocks at 4e10 blocks/s = 1 ms, over 4 ms of kernel
    r = _run(ops={"mh_vocab_window.3": 0.004, "fusion.1": 1.0})
    assert m.read(r) == pytest.approx(25.0)
    # a program without the kernel (the parent of this metric) reads None
    assert m.read(_run(ops={"fusion.1": 1.0})) is None


def test_mh_roofline_takes_the_larger_leg_of_a_decode_step():
    m = _metric("mh_roofline")
    # hbm leg: 10 x 1.32e8 B / 8e11 B/s = 1.65 ms over 2 s busy
    assert m.read(_run()) == pytest.approx(100 * 1.65e-3 / 2.0)
    assert m.read(_run(cfg={"update": "gibbs"})) is None


def test_table_prep_pct_without_a_trace_file_reads_none(tmp_path,
                                                        monkeypatch):
    m = _metric("table_prep_pct")
    monkeypatch.setattr(m, "TRACES", tmp_path)
    assert m.read(_run()) is None
    assert m.read(_run(cfg={"workload": "gmm"})) is None
