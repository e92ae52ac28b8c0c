"""The telemetry subsystem (DESIGN.md §Telemetry): tracing core, metrics
registry, health monitor — and the two contracts the instrumentation
must honour: the sampled stream is bit-identical with telemetry on vs
off, and the exporters emit valid, schema-checked files."""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers, telemetry
from repro.checkpoint import run_resumable
from repro.diagnostics import SwapStats
from repro.launch import monitor as monitor_cli
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.tracing import Tracer
from repro.workloads.ising import IsingModel

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(autouse=True)
def _clean_global_telemetry():
    """Tests share the process-default tracer/registry: leave both off
    and empty regardless of what a test did."""
    yield
    telemetry.disable()
    telemetry.TRACER.reset()
    telemetry.REGISTRY.reset()


def _mh_setup(seed=0):
    table = jax.random.normal(jax.random.PRNGKey(seed), (2, 64), jnp.float32)
    target = samplers.TableTarget(table)
    init = jnp.broadcast_to(
        jnp.argmax(table, -1).astype(jnp.uint32)[:, None], (2, 8)
    )
    return target, init


def _gibbs_setup(seed=1):
    model = IsingModel(height=6, width=6)
    return model, model.random_init(jax.random.PRNGKey(seed), 2)


# --------------------------------------------------------------------------
# tracing core
# --------------------------------------------------------------------------


class TestTracer:
    def test_span_nesting_and_ordering(self):
        tr = Tracer()
        tr.enabled = True
        with tr.span("outer", a=1):
            with tr.span("inner"):
                pass
            with tr.span("inner2"):
                pass
        evs = tr.events()
        # spans record on exit: inner events precede the outer one
        assert [e.name for e in evs] == ["inner", "inner2", "outer"]
        assert [e.depth for e in evs] == [1, 1, 0]
        assert [e.seq for e in evs] == [0, 1, 2]
        assert all(e.dur_us >= 0 for e in evs)
        outer = evs[-1]
        assert outer.meta == {"a": 1}
        # the outer span covers its children in time
        assert outer.ts_us <= evs[0].ts_us
        assert outer.ts_us + outer.dur_us >= evs[1].ts_us + evs[1].dur_us

    def test_disabled_span_is_shared_noop(self):
        tr = Tracer()
        s1 = tr.span("x", big=1)
        s2 = tr.span("y")
        assert s1 is s2  # no allocation on the disabled path
        with s1 as s:
            s.set(late="metadata")  # no-op parity with the live span
        assert tr.events() == []

    def test_late_metadata_via_set(self):
        tr = Tracer()
        tr.enabled = True
        with tr.span("submit") as sp:
            sp.set(jit_cache="miss")
        (ev,) = tr.events()
        assert ev.meta["jit_cache"] == "miss"

    def test_meta_cleaned_to_json_scalars(self):
        tr = Tracer()
        tr.enabled = True
        with tr.span("s", arr=np.arange(3), ok=2.5, flag=True, none=None):
            pass
        (ev,) = tr.events()
        assert ev.meta["ok"] == 2.5 and ev.meta["flag"] is True
        assert ev.meta["none"] is None
        assert isinstance(ev.meta["arr"], str)  # repr()'d, never a crash
        json.dumps(ev.to_json())  # always serialisable

    def test_ring_overflow_drops_oldest(self):
        tr = Tracer(capacity=4)
        tr.enabled = True
        for i in range(7):
            tr.instant(f"e{i}")
        evs = tr.events()
        assert len(evs) == 4
        assert [e.name for e in evs] == ["e3", "e4", "e5", "e6"]
        assert tr.dropped == 3

    def test_reset_restarts_epoch_and_seq(self):
        tr = Tracer()
        tr.enabled = True
        tr.instant("a")
        tr.reset()
        assert tr.events() == [] and tr.dropped == 0
        tr.instant("b")
        assert tr.events()[0].seq == 0

    def test_export_jsonl_roundtrip_and_validate(self, tmp_path):
        tr = Tracer()
        tr.enabled = True
        with tr.span("s", k="v"):
            tr.instant("i", n=2)
        path = str(tmp_path / "out.trace.jsonl")
        n = tr.export_jsonl(path)
        assert n == 2
        assert telemetry.validate_jsonl(path) == []
        lines = [json.loads(line) for line in open(path)]
        assert lines[0]["kind"] == "trace_meta"
        assert lines[0]["schema"] == telemetry.SCHEMA_VERSION
        assert lines[0]["events"] == 2 and lines[0]["dropped"] == 0

    def test_export_chrome_trace_is_valid(self, tmp_path):
        tr = Tracer()
        tr.enabled = True
        with tr.span("seg", step0=4):
            tr.instant("mark")
        path = str(tmp_path / "out.trace.json")
        tr.export_chrome_trace(path)
        with open(path) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        assert len(evs) == 2
        span = next(e for e in evs if e["ph"] == "X")
        assert span["name"] == "seg" and span["dur"] >= 0
        assert {"ts", "pid", "tid"} <= span.keys()
        inst = next(e for e in evs if e["ph"] == "i")
        assert inst["name"] == "mark"
        assert doc["otherData"]["schema"] == telemetry.SCHEMA_VERSION

    def test_export_format_by_extension(self, tmp_path):
        tr = Tracer()
        tr.enabled = True
        tr.instant("x")
        chrome = str(tmp_path / "a.json")
        jsonl = str(tmp_path / "a.trace.jsonl")
        tr.export(chrome)
        tr.export(jsonl)
        json.load(open(chrome))  # one JSON object
        assert telemetry.validate_jsonl(jsonl) == []

    def test_validate_rejects_bad_events(self, tmp_path):
        bad = tmp_path / "bad.jsonl"
        bad.write_text(
            json.dumps({"kind": "span", "name": "s", "ts_us": 0.0, "seq": 0})
            + "\nnot json\n"
            + json.dumps({"kind": "mystery", "name": "x"})
            + "\n"
        )
        problems = telemetry.validate_jsonl(str(bad))
        assert len(problems) == 3  # span w/o dur, non-JSON, unknown kind
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert telemetry.validate_jsonl(str(empty)) == ["empty trace file"]

    def test_log_records_instant_only_when_enabled(self):
        tr = Tracer()
        tr.log("quiet", a=1)
        assert tr.events() == []
        tr.enabled = True
        tr.log("loud", a=1)
        (ev,) = tr.events()
        assert ev.kind == "instant" and ev.meta == {"a": 1}


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------


class TestMetrics:
    def test_counter_label_aggregation(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total")
        c.inc(workload="ising")
        c.inc(2, workload="ising")
        c.inc(workload="gmm")
        c.inc()  # label-less series is its own bucket
        assert c.value(workload="ising") == 3
        assert c.value(workload="gmm") == 1
        assert c.value() == 1
        snap = reg.snapshot()["requests_total"]
        assert snap["type"] == "counter"
        assert snap["values"]["workload=ising"] == 3

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("c").inc(-1)

    def test_gauge_overwrites(self):
        g = MetricsRegistry().gauge("depth")
        g.set(3)
        g.set(1)
        assert g.value() == 1

    def test_histogram_buckets_and_stats(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v, workload="ising")
        stats = h.snapshot()["workload=ising"]
        assert stats["count"] == 4
        assert stats["sum"] == pytest.approx(5.555)
        assert stats["buckets"] == {
            "le_0.01": 1, "le_0.1": 1, "le_1": 1, "le_inf": 1
        }

    def test_registry_typechecks_reuse(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("req_total", "requests").inc(2, workload="ising")
        reg.histogram("lat_s", buckets=(0.1, 1.0)).observe(0.05)
        text = reg.prometheus_text()
        assert '# TYPE req_total counter' in text
        assert 'req_total{workload="ising"} 2' in text
        # cumulative le buckets + sum/count series
        assert 'lat_s_bucket{le="0.1"} 1' in text
        assert 'lat_s_bucket{le="+Inf"} 1' in text
        assert 'lat_s_count 1' in text

    def test_flush_jsonl_appends_snapshots(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("n").inc()
        path = str(tmp_path / "metrics.jsonl")
        reg.flush_jsonl(path)
        reg.counter("n").inc()
        reg.flush_jsonl(path)
        lines = [json.loads(line) for line in open(path)]
        assert len(lines) == 2
        assert lines[0]["metrics"]["n"]["values"][""] == 1
        assert lines[1]["metrics"]["n"]["values"][""] == 2

    def test_jsonl_flusher_rate_limits(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("n").inc()
        path = str(tmp_path / "m.jsonl")
        fl = telemetry.JsonlFlusher(reg, path, interval_s=3600.0)
        assert fl.maybe_flush() is True
        assert fl.maybe_flush() is False  # within the interval
        fl.close()  # final snapshot is unconditional
        assert len(open(path).readlines()) == 2


# --------------------------------------------------------------------------
# health monitor
# --------------------------------------------------------------------------


class TestHealth:
    def test_acceptance_collapse_warns(self):
        mon = telemetry.HealthMonitor()
        with pytest.warns(telemetry.SamplerHealthWarning, match="collapse"):
            alerts = mon.check_acceptance(0.0, where="ising")
        assert [a.kind for a in alerts] == ["acceptance_collapse"]
        assert alerts[0].severity == "critical"
        assert alerts[0].data["rate"] == 0.0
        assert mon.alerts == alerts

    def test_healthy_rate_is_silent(self):
        mon = telemetry.HealthMonitor()
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert mon.check_acceptance(0.3) == []

    def test_acceptance_saturation_threshold(self):
        mon = telemetry.HealthMonitor(
            telemetry.HealthThresholds(max_acceptance=0.99), warn=False
        )
        assert [a.kind for a in mon.check_acceptance(0.999)] == [
            "acceptance_saturated"
        ]

    def test_rhat_divergence_from_dict_and_nonfinite(self):
        mon = telemetry.HealthMonitor(warn=False)
        assert mon.check_chain_stats({"split_rhat": 1.01}) == []
        (a,) = mon.check_chain_stats({"split_rhat": 2.5}, where="gmm")
        assert a.kind == "rhat_divergence" and "gmm" in a.message
        (b,) = mon.check_chain_stats({"split_rhat": float("nan")})
        assert b.kind == "rhat_divergence"

    def test_swap_bottleneck_and_stalled_walkers(self):
        stats = SwapStats(3, ())
        attempted = np.array([True, False])
        rejected = np.zeros((2,), bool)
        for _ in range(10):  # ≥ stall_events rejected swap events
            stats.record(attempted, rejected)
        mon = telemetry.HealthMonitor(warn=False)
        kinds = [a.kind for a in mon.check_swap_stats(stats)]
        assert kinds == ["swap_bottleneck", "stalled_walkers"]
        pair0 = mon.alerts[0]
        assert pair0.data["pair"] == 0 and pair0.data["rate"] == 0.0

    def test_untried_pair_is_not_a_bottleneck(self):
        stats = SwapStats(3, ())  # no events at all: rates are NaN
        mon = telemetry.HealthMonitor(warn=False)
        assert mon.check_swap_stats(stats) == []

    def test_serving_slo_breaches(self):
        mon = telemetry.HealthMonitor(
            telemetry.HealthThresholds(
                p99_latency_slo_s=1.0, max_wait_slo_s=0.5
            ),
            warn=False,
        )
        summary = {"p99_latency_s": 2.0, "p99_wait_s": 0.7}
        kinds = [a.kind for a in mon.check_serving(summary)]
        assert kinds == ["latency_slo_breach", "wait_slo_breach"]
        assert mon.alerts[0].severity == "critical"
        # within SLO: silent
        assert (
            mon.check_serving({"p99_latency_s": 0.5, "p99_wait_s": 0.1}) == []
        )

    def test_alerts_counted_in_metrics(self):
        mon = telemetry.HealthMonitor(warn=False)
        mon.check_acceptance(0.0)
        c = telemetry.REGISTRY.counter("sampler_health_alerts_total")
        assert c.value(kind="acceptance_collapse") == 1


# --------------------------------------------------------------------------
# instrumented layers: bit-parity + emitted events
# --------------------------------------------------------------------------


def _children(events, parent):
    """Spans one level under ``parent`` on its thread, inside its time."""
    end = parent.ts_us + parent.dur_us
    return [
        e for e in events
        if e.kind == "span" and e.tid == parent.tid
        and e.depth == parent.depth + 1
        and parent.ts_us <= e.ts_us and e.ts_us + e.dur_us <= end
    ]


class TestInstrumentation:
    @pytest.mark.parametrize(
        "update,execution",
        [
            pytest.param("mh", "scan", id="mh"),
            pytest.param("gibbs", "scan", id="gibbs"),
            pytest.param("mh", "pallas", id="mh-pallas"),
            pytest.param("gibbs", "pallas", id="gibbs-pallas"),
        ],
    )
    def test_submit_bit_parity_tracing_on_vs_off(self, update, execution):
        """The overhead contract's numerical half: tracing must never
        touch the sampled stream, on the scan executor or the eager
        Pallas chunk loop."""
        target, init = _gibbs_setup() if update == "gibbs" else _mh_setup()
        engine = samplers.MHEngine(
            samplers.EngineConfig(
                update=update, chunk_steps=8, execution=execution
            )
        )
        plan = samplers.RunPlan(
            target=target, n_steps=20, init_words=init, seed=5
        )
        off = engine.submit(plan).result
        telemetry.enable()
        on = engine.submit(plan).result
        telemetry.disable()
        np.testing.assert_array_equal(
            np.asarray(off.samples), np.asarray(on.samples)
        )
        np.testing.assert_array_equal(
            np.asarray(off.final_words), np.asarray(on.final_words)
        )
        np.testing.assert_array_equal(
            np.asarray(off.final_logp), np.asarray(on.final_logp)
        )

    def test_submit_span_carries_plan_metadata(self):
        target, init = _mh_setup()
        engine = samplers.MHEngine(samplers.EngineConfig(chunk_steps=8))
        plan = samplers.RunPlan(
            target=target, n_steps=12, init_words=init, seed=2
        )
        tr = telemetry.enable()
        engine.submit(plan)
        spans = [e for e in tr.events() if e.name == "engine.submit"]
        assert len(spans) == 1
        meta = spans[0].meta
        assert meta["n_steps"] == 12 and meta["update"] == "mh"
        assert meta["compiled"] is False

    def test_compiled_submit_records_jit_cache_verdict(self):
        target, init = _mh_setup()
        engine = samplers.MHEngine(samplers.EngineConfig(chunk_steps=8))
        plan = samplers.RunPlan(
            target=target, n_steps=12, init_words=init, seed=2
        )
        tr = telemetry.enable()
        engine.submit(plan, compiled=True)
        engine.submit(plan, compiled=True)
        verdicts = [
            e.meta.get("jit_cache")
            for e in tr.events()
            if e.name == "engine.submit"
        ]
        assert verdicts == ["miss", "hit"]

    def test_submit_inside_a_trace_emits_no_span(self):
        """Spans are host-side only: a submit re-entered inside a jax
        trace (as serving's class programs and tempering's segments do)
        records nothing."""
        from repro.samplers.plan import _host_side

        target, init = _mh_setup()
        engine = samplers.MHEngine(samplers.EngineConfig(chunk_steps=8))
        inside = []

        @jax.jit
        def run(words):
            inside.append(_host_side())
            plan = samplers.RunPlan(
                target=target, n_steps=12, init_words=words, seed=2
            )
            return engine.submit(plan).result.final_words

        tr = telemetry.enable()
        run(init)
        assert _host_side() and inside == [False]
        assert not [e for e in tr.events() if e.name == "engine.submit"]

    @pytest.mark.parametrize(
        "update,randomness",
        [("mh", "cim"), ("mh", "fused"), ("gibbs", "host"),
         ("gibbs", "fused")],
    )
    def test_pallas_chunk_loop_spans(self, update, randomness):
        """The eager Pallas chunk loop records one ``engine.chunk`` per
        chunk under ``engine.submit``, each holding one ``engine.emit``
        and, where operands are drawn on the host, one
        ``randomness.draw``; one ``engine.finish`` follows the loop."""
        target, init = _gibbs_setup() if update == "gibbs" else _mh_setup()
        engine = samplers.MHEngine(
            samplers.EngineConfig(
                update=update, randomness=randomness, execution="pallas",
                chunk_steps=8,
            )
        )
        plan = samplers.RunPlan(
            target=target, n_steps=20, init_words=init, seed=3
        )
        tr = telemetry.enable()
        engine.submit(plan)
        evs = tr.events()
        (submit,) = [e for e in evs if e.name == "engine.submit"]
        chunks = [e for e in evs if e.name == "engine.chunk"]
        assert len(chunks) == -(-20 // 8)
        assert chunks == [
            e for e in _children(evs, submit) if e.name == "engine.chunk"
        ]
        assert [(e.meta["start"], e.meta["n"]) for e in chunks] == [
            (0, 8), (8, 8), (16, 4)
        ]
        (finish,) = [
            e for e in _children(evs, submit) if e.name == "engine.finish"
        ]
        assert finish.ts_us >= chunks[-1].ts_us + chunks[-1].dur_us
        draws = [e for e in evs if e.name == "randomness.draw"]
        for c in chunks:
            kids = sorted(
                e.name for e in _children(evs, c) if e.name != "jax.compile"
            )
            if randomness == "fused":
                assert kids == ["engine.emit"]
            else:
                assert kids == ["engine.emit", "randomness.draw"]
        if randomness == "fused":
            assert draws == []
        else:
            assert len(draws) == len(chunks)
            assert {e.meta["backend"] for e in draws} == {randomness}
            assert [e.meta["n"] for e in draws] == [8, 8, 4]

    def test_draw_spans_record_jit_cache_verdict(self):
        """Each eager cim ``randomness.draw`` span says whether the
        compiled draw compiled: ``miss`` on the first chunk, ``hit`` on
        every later one (equal lengths, later starts, a second job); the
        same run staged inside ``jax.jit`` records no draw span."""
        from repro.samplers.randomness import _chunk_program

        target, init = _mh_setup()
        engine = samplers.MHEngine(
            samplers.EngineConfig(
                randomness="cim", execution="pallas", chunk_steps=8
            )
        )
        plan = samplers.RunPlan(
            target=target, n_steps=24, init_words=init, seed=5
        )
        _chunk_program.clear_cache()  # the first chunk must compile
        tr = telemetry.enable()
        engine.submit(plan)
        engine.submit(plan)

        def verdicts():
            return [
                e.meta.get("jit_cache")
                for e in tr.events() if e.name == "randomness.draw"
            ]

        assert verdicts() == ["miss"] + ["hit"] * 5
        jax.jit(
            lambda w: engine.run(
                jax.random.PRNGKey(5), target, 24, w
            ).final_words
        )(init)
        assert len(verdicts()) == 6

    def test_chunk_spans_absent_when_traced(self):
        """The chunk loop staged under a trace (the compiled submit, or
        a caller's ``jax.jit``) records none of its spans."""
        target, init = _mh_setup()
        engine = samplers.MHEngine(
            samplers.EngineConfig(
                randomness="cim", execution="pallas", chunk_steps=8
            )
        )
        plan = samplers.RunPlan(
            target=target, n_steps=12, init_words=init, seed=2
        )

        @jax.jit
        def run(words):
            return engine.run(
                jax.random.PRNGKey(2), target, 12, words
            ).final_words

        tr = telemetry.enable()
        engine.submit(plan, compiled=True)
        run(init)
        names = {e.name for e in tr.events()}
        assert "engine.submit" in names
        assert not names & {
            "engine.chunk", "engine.emit", "randomness.draw", "engine.finish"
        }

    def test_run_resumable_emits_segment_logs(self, tmp_path):
        target, init = _mh_setup()
        engine = samplers.MHEngine(samplers.EngineConfig(chunk_steps=8))
        plan = samplers.RunPlan(
            target=target, n_steps=16, init_words=init, seed=7
        )
        tr = telemetry.enable()
        run_resumable(engine, plan, directory=str(tmp_path), every=8)
        segs = [e for e in tr.events() if e.name == "run_resumable.segment"]
        assert len(segs) == 2
        assert [e.meta["segment"] for e in segs] == [0, 1]
        assert [e.meta["done"] for e in segs] == [8, 16]
        for e in segs:
            assert e.meta["bytes"] > 0
            assert len(e.meta["fingerprint"]) == 12  # sha256 digest prefix
        saves = [e for e in tr.events() if e.name == "checkpoint.save"]
        assert len(saves) == 2 and all(e.meta["bytes"] > 0 for e in saves)

    def test_run_resumable_restore_log_and_parity(self, tmp_path):
        target, init = _mh_setup()
        engine = samplers.MHEngine(samplers.EngineConfig(chunk_steps=8))
        plan = samplers.RunPlan(
            target=target, n_steps=16, init_words=init, seed=7
        )
        ref = engine.submit(plan).result
        boom = RuntimeError("preempted")

        def die_once(done, total, handle):
            if done == 8:
                raise boom

        with pytest.raises(RuntimeError):
            run_resumable(
                engine, plan, directory=str(tmp_path), every=8,
                on_segment=die_once,
            )
        tr = telemetry.enable()
        handle = run_resumable(engine, plan, directory=str(tmp_path), every=8)
        restores = [
            e for e in tr.events() if e.name == "run_resumable.restore"
        ]
        assert len(restores) == 1 and restores[0].meta["done"] == 8
        np.testing.assert_array_equal(
            np.asarray(handle.result.final_words), np.asarray(ref.final_words)
        )

    def test_serving_emits_segment_spans_and_latency_split(self):
        from repro.serving import Scheduler, ServeRequest, latency_summary

        tr = telemetry.enable()
        sched = Scheduler(n_slots=2, smoke=True, workload_kwargs={})
        reqs = [
            ServeRequest(rid=i, workload="gmm", n_steps=8, seed=i)
            for i in range(2)
        ]
        done = sched.serve(reqs)
        assert all(r.t_done is not None for r in done)
        for r in done:
            assert r.service_s is not None and r.service_s >= 0
            assert abs(r.wait_s + r.service_s - r.latency_s) < 1e-9
        summary = latency_summary(done)
        for k in (
            "p99_wait_s", "mean_service_s", "p50_service_s", "p99_service_s"
        ):
            assert k in summary
        names = {e.name for e in tr.events()}
        assert "serving.segment" in names and "serving.finalize" in names
        reg = telemetry.REGISTRY
        assert reg.counter("serving_requests_admitted_total").value(
            workload="gmm"
        ) == 2
        assert reg.counter("serving_requests_retired_total").value() == 2

    def test_serving_loop_spans(self):
        """One ``serving.admit`` per admitted request; the segment's
        inputs, dispatch and bookkeeping nest one level under
        ``serving.segment``; a real-time wait for an arrival with no slot
        busy is ``serving.idle``."""
        from repro.serving import Scheduler, ServeRequest

        tr = telemetry.enable()
        sched = Scheduler(n_slots=2, smoke=True, workload_kwargs={})
        reqs = [
            ServeRequest(rid=i, workload="gmm", n_steps=8, seed=i,
                         t_arrive=0.05)
            for i in range(3)
        ]
        sched.serve(reqs, realtime=True)
        evs = tr.events()
        admits = [e for e in evs if e.name == "serving.admit"]
        assert len(admits) == 3
        assert {e.meta["workload"] for e in admits} == {"gmm"}
        assert sorted(e.meta["slot"] for e in admits) == [0, 0, 1]
        segments = [e for e in evs if e.name == "serving.segment"]
        assert segments
        for seg in segments:
            kids = [
                e.name for e in _children(evs, seg)
                if e.name != "jax.compile"
            ]
            assert kids == [
                "serving.inputs", "serving.dispatch", "serving.bookkeep"
            ]
        idle = [e for e in evs if e.name == "serving.idle"]
        assert idle and all(e.depth == 0 for e in idle)

    def test_spans_land_on_the_profiler_host_plane(self, tmp_path):
        """While enabled, every span is mirrored as a profiler
        annotation: a ``jax.profiler`` trace holds it by name on a
        ``/host:`` plane."""
        import glob

        tr = telemetry.enable()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("test.mirrored", n=3):
                jnp.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(
            str(tmp_path / "**" / "*.xplane.pb"), recursive=True
        )
        found = [
            ev
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines
            for ev in line.events
            if ev.name == "test.mirrored"
        ]
        assert len(found) == 1 and found[0].duration_ns > 0

    def test_compile_recorded_under_the_open_span(self):
        """A backend compile while tracing is a ``jax.compile`` span
        nested in the span that triggered it."""
        from jax.experimental.compilation_cache import compilation_cache

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            x = jnp.arange(5.0)
            fresh = jax.jit(lambda v: v * 3.25 - 1.0)
            tr = telemetry.enable()
            with tr.span("outer"):
                fresh(x).block_until_ready()
            fresh(x).block_until_ready()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
        evs = tr.events()
        (outer,) = [e for e in evs if e.name == "outer"]
        compiles = [e for e in evs if e.name == "jax.compile"]
        assert len(compiles) == 1
        assert compiles == _children(evs, outer)
        assert compiles[0].dur_us > 0

    def test_tempering_emits_swap_spans(self):
        from repro import tempering

        model, init1 = _gibbs_setup()
        engine = samplers.MHEngine(
            samplers.EngineConfig(update="gibbs", chunk_steps=8)
        )
        ladder = tempering.Ladder.geometric(2, beta_min=0.5)
        rex = tempering.ReplicaExchange(
            ladder=ladder, engine=engine, swap_every=8
        )
        init = jnp.broadcast_to(init1, (2, *init1.shape))
        tr = telemetry.enable()
        rex.run(jax.random.PRNGKey(0), model, 24, init)
        names = [e.name for e in tr.events()]
        assert names.count("tempering.segment") == 3
        assert names.count("tempering.swap") == 2


# --------------------------------------------------------------------------
# monitor CLI
# --------------------------------------------------------------------------


class TestMonitorCLI:
    def _write_trace(self, tmp_path) -> str:
        tr = telemetry.enable()
        with tr.span("engine.submit", n_steps=4):
            pass
        tr.log("health.rhat_divergence", split_rhat=2.0)
        path = str(tmp_path / "out.trace.jsonl")
        tr.export_jsonl(path)
        telemetry.disable()
        return path

    def test_check_valid_trace(self, tmp_path, capsys):
        path = self._write_trace(tmp_path)
        assert monitor_cli.main(["--check", path]) == 0
        assert "valid trace" in capsys.readouterr().out

    def test_check_invalid_trace_exits_nonzero(self, tmp_path, capsys):
        bad = tmp_path / "bad.trace.jsonl"
        bad.write_text('{"kind": "span", "name": ""}\n')
        assert monitor_cli.main(["--check", str(bad)]) == 1
        assert "INVALID" in capsys.readouterr().out

    def test_summary_aggregates_spans(self, tmp_path, capsys):
        path = self._write_trace(tmp_path)
        assert monitor_cli.main([path]) == 0
        out = capsys.readouterr().out
        assert "span=engine.submit" in out and "count=1" in out
        assert "health.rhat_divergence" in out

    def test_summarize_events_shares(self):
        events = [
            {"kind": "span", "name": "a", "dur_us": 30.0},
            {"kind": "span", "name": "a", "dur_us": 10.0},
            {"kind": "span", "name": "b", "dur_us": 60.0},
        ]
        rows = monitor_cli.summarize_events(events)
        assert rows[0]["span"] == "b" and rows[0]["share"] == 0.6
        assert rows[1]["span"] == "a" and rows[1]["count"] == 2
        # nested: a child's time is its own, not its parent's as well
        nested = [
            {"kind": "span", "name": "submit", "ts_us": 0.0,
             "dur_us": 100.0, "tid": 0, "depth": 0},
            {"kind": "span", "name": "chunk", "ts_us": 10.0,
             "dur_us": 40.0, "tid": 0, "depth": 1},
            {"kind": "span", "name": "emit", "ts_us": 30.0,
             "dur_us": 10.0, "tid": 0, "depth": 2},
            {"kind": "span", "name": "chunk", "ts_us": 55.0,
             "dur_us": 40.0, "tid": 0, "depth": 1},
            # another thread's top-level span at the same time
            {"kind": "span", "name": "other", "ts_us": 20.0,
             "dur_us": 100.0, "tid": 1, "depth": 0},
        ]
        rows = {r["span"]: r for r in monitor_cli.summarize_events(nested)}
        assert rows["chunk"]["total_ms"] == 0.08
        assert rows["submit"]["total_ms"] == 0.1
        assert rows["submit"]["share"] == 0.1    # 20 of 200 us
        assert rows["chunk"]["share"] == 0.35    # 70 of 200 us
        assert rows["emit"]["share"] == 0.05
        assert rows["other"]["share"] == 0.5
        assert sum(r["share"] for r in rows.values()) == pytest.approx(1.0)
