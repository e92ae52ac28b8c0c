"""The run API (DESIGN.md §Run-API): RunPlan validation, submit parity,
handle resume, the deprecated shims' bit-compatibility, and the
autotuner's never-slower + cache contracts."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers
from repro.workloads.ising import IsingModel

jax.config.update("jax_platform_name", "cpu")


def _mh_setup(b=2, v=64, c=8, seed=0):
    table = jax.random.normal(jax.random.PRNGKey(seed), (b, v), jnp.float32)
    target = samplers.TableTarget(table)
    init = jnp.broadcast_to(
        jnp.argmax(table, -1).astype(jnp.uint32)[:, None], (b, c)
    )
    return target, init


class TestRunPlanValidation:
    def test_key_xor_seed(self):
        target, init = _mh_setup()
        with pytest.raises(ValueError, match="exactly one of"):
            samplers.RunPlan(target=target, n_steps=4, init_words=init)
        with pytest.raises(ValueError, match="exactly one of"):
            samplers.RunPlan(
                target=target, n_steps=4, init_words=init,
                key=jax.random.PRNGKey(0), seed=1,
            )

    def test_init_words_required(self):
        target, _ = _mh_setup()
        with pytest.raises(ValueError, match="init_words is required"):
            samplers.RunPlan(
                target=target, n_steps=4, init_words=None, seed=0
            )

    def test_bad_n_steps_step0_collect(self):
        target, init = _mh_setup()
        with pytest.raises(ValueError, match="n_steps"):
            samplers.RunPlan(
                target=target, n_steps=0, init_words=init, seed=0
            )
        with pytest.raises(ValueError, match="step0"):
            samplers.RunPlan(
                target=target, n_steps=4, init_words=init, seed=0, step0=-1
            )
        with pytest.raises(ValueError):
            samplers.RunPlan(
                target=target, n_steps=4, init_words=init, seed=0,
                collect="thin:0",
            )

    def test_seed_resolves_to_prngkey(self):
        target, init = _mh_setup()
        plan = samplers.RunPlan(
            target=target, n_steps=4, init_words=init, seed=7
        )
        np.testing.assert_array_equal(
            np.asarray(plan.resolved_key()),
            np.asarray(jax.random.PRNGKey(7)),
        )

    def test_submit_rejects_non_plan(self):
        engine = samplers.MHEngine(samplers.EngineConfig())
        with pytest.raises(TypeError, match="RunPlan"):
            engine.submit({"n_steps": 4})


class TestSubmitParity:
    @pytest.mark.parametrize("compiled", [False, True])
    def test_submit_matches_engine_run(self, compiled):
        target, init = _mh_setup()
        engine = samplers.MHEngine(samplers.EngineConfig(chunk_steps=8))
        key = jax.random.PRNGKey(3)
        ref = engine.run(key, target, 24, init)
        handle = engine.submit(
            samplers.RunPlan(
                target=target, n_steps=24, init_words=init, key=key
            ),
            compiled=compiled,
        )
        np.testing.assert_array_equal(
            np.asarray(handle.samples), np.asarray(ref.samples)
        )
        np.testing.assert_array_equal(
            np.asarray(handle.accept_count), np.asarray(ref.accept_count)
        )
        np.testing.assert_array_equal(
            np.asarray(handle.final_words), np.asarray(ref.final_words)
        )

    def test_handle_resume_is_segment_invariant(self):
        target, init = _mh_setup()
        engine = samplers.MHEngine(samplers.EngineConfig(chunk_steps=8))
        key = jax.random.PRNGKey(5)
        mono = engine.run(key, target, 32, init)
        h1 = engine.submit(
            samplers.RunPlan(
                target=target, n_steps=12, init_words=init, key=key
            )
        )
        h2 = h1.resume(20)
        assert h1.progress == 12 and h2.progress == 32
        np.testing.assert_array_equal(
            np.concatenate(
                [np.asarray(h1.samples), np.asarray(h2.samples)], axis=0
            ),
            np.asarray(mono.samples),
        )
        np.testing.assert_array_equal(
            np.asarray(h1.accept_count) + np.asarray(h2.accept_count),
            np.asarray(mono.accept_count),
        )
        np.testing.assert_array_equal(
            np.asarray(h2.final_words), np.asarray(mono.final_words)
        )

    def test_gibbs_resume_segment_invariant(self):
        model = IsingModel(height=6, width=6)
        init = model.random_init(jax.random.PRNGKey(1), 2)
        engine = samplers.MHEngine(
            samplers.EngineConfig(update="gibbs", chunk_steps=8)
        )
        key = jax.random.PRNGKey(9)
        mono = engine.run(key, model, 20, init)
        h1 = engine.submit(
            samplers.RunPlan(target=model, n_steps=8, init_words=init, key=key)
        )
        h2 = h1.resume(12)
        np.testing.assert_array_equal(
            np.concatenate(
                [np.asarray(h1.samples), np.asarray(h2.samples)], axis=0
            ),
            np.asarray(mono.samples),
        )

    def test_traced_step0_goes_through_submit(self):
        """Plans with traced offsets stay traceable (the serving-tier
        pattern); compiled=True silently takes the direct path."""
        target, init = _mh_setup()
        engine = samplers.MHEngine(samplers.EngineConfig(chunk_steps=8))
        key = jax.random.PRNGKey(2)

        @jax.jit
        def seg(step0, words):
            res = engine.submit(
                samplers.RunPlan(
                    target=target, n_steps=8, init_words=words, key=key,
                    step0=step0,
                ),
                compiled=True,
            ).result
            return res.samples, res.final_words

        mono = engine.run(key, target, 16, init)
        s1, w1 = seg(jnp.int32(0), init)
        s2, _ = seg(jnp.int32(8), w1)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(s1), np.asarray(s2)]),
            np.asarray(mono.samples),
        )

    def test_thin_traced_step0_error_names_fallback(self):
        """The thin + traced step0 error must spell out both escapes:
        concrete step0, or collect='all' + the host strided slice the
        serving tier uses."""
        target, init = _mh_setup()
        engine = samplers.MHEngine(
            samplers.EngineConfig(collect="thin:4", chunk_steps=8)
        )
        key = jax.random.PRNGKey(0)
        with pytest.raises(Exception) as e:

            @jax.jit
            def seg(step0):
                return engine.run(key, target, 8, init, step0=step0).samples

            seg(jnp.int32(8))
        msg = str(e.value)
        assert "concrete" in msg or "python int" in msg
        assert "samples[(-step0) % k :: k]" in msg
        assert "serving" in msg


class TestDeprecatedShims:
    def test_run_engine_warns_and_matches(self):
        target, init = _mh_setup()
        engine = samplers.MHEngine(samplers.EngineConfig(chunk_steps=8))
        key = jax.random.PRNGKey(4)
        ref = engine.run(key, target, 16, init)
        with pytest.warns(DeprecationWarning, match="RunPlan"):
            old = samplers.run_engine(
                key, init, engine=engine, target=target, n_steps=16
            )
        np.testing.assert_array_equal(
            np.asarray(old.samples), np.asarray(ref.samples)
        )

    def test_run_chain_warns_and_matches_impl(self):
        from repro.core import metropolis

        cfg = metropolis.MHConfig(nbits=4, burn_in=8, thin=2, chunk_steps=8)
        key = jax.random.PRNGKey(0)

        def logp(x):
            return -0.1 * (x.astype(jnp.float32) - 5.0) ** 2

        with pytest.warns(DeprecationWarning, match="RunPlan"):
            old = metropolis.run_chain(key, logp, cfg, 6, chain_shape=(4,))
        new = metropolis._run_chain_impl(key, logp, cfg, 6, chain_shape=(4,))
        np.testing.assert_array_equal(
            np.asarray(old.samples), np.asarray(new.samples)
        )

    def test_sample_tokens_warns_and_matches_impl(self):
        from repro.core import token_sampler

        cfg = token_sampler.TokenSamplerConfig(vocab_size=50, n_steps=16)
        key = jax.random.PRNGKey(0)
        logits = jax.random.normal(jax.random.PRNGKey(1), (3, 50))
        with pytest.warns(DeprecationWarning, match="sample_tokens"):
            old = token_sampler.sample_tokens(key, logits, cfg)
        new = token_sampler._sample_tokens_impl(key, logits, cfg)
        np.testing.assert_array_equal(
            np.asarray(old.tokens), np.asarray(new.tokens)
        )

    def test_documented_surface_exports(self):
        for name in (
            "RunPlan", "RunHandle", "submit", "TuneResult",
            "autotune_config", "autotune_engine", "run_engine",
        ):
            assert name in samplers.__all__, name

    def test_internal_callers_do_not_warn(self):
        """Production paths route around the shims — the warning belongs
        to external callers only."""
        from repro.core import macro

        m = macro.CIMMacro(
            macro.MacroConfig(nbits=4, n_compartments=8, burn_in=16)
        )
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            m.sample(
                jax.random.PRNGKey(0),
                lambda x: -0.05 * (x.astype(jnp.float32) - 3.0) ** 2,
                n_samples=8,
            )


class TestAutotune:
    def test_measured_then_cached_never_slower(self, tmp_path):
        target, init = _mh_setup(c=16)
        cfg = samplers.EngineConfig(chunk_steps=32, execution="scan")
        cache = str(tmp_path / "autotune.json")
        tuned_cfg, res = samplers.autotune_config(
            cfg, target, init, n_steps=32, repeats=1,
            chunk_candidates=(16, 64), cache_path=cache,
        )
        assert res.source == "measured"
        # the incumbent is candidate 0 and the winner is the argmax
        assert res.candidates[0][:3] == (32, cfg.block_c, "scan")
        assert res.steps_per_s >= res.baseline_steps_per_s
        assert tuned_cfg.chunk_steps == res.chunk_steps
        # second call hits the cache without measuring
        tuned2, res2 = samplers.autotune_config(
            cfg, target, init, n_steps=32, repeats=1,
            chunk_candidates=(16, 64), cache_path=cache,
        )
        assert res2.source == "cache"
        assert tuned2 == tuned_cfg

    @pytest.mark.parametrize("dropped", [True, False])
    def test_only_ineligible_candidates_drop(self, tmp_path, monkeypatch,
                                              dropped):
        """A candidate the engine refuses (IneligibleExecution) is
        dropped; any other failure, e.g. a compiler refusing a kernel,
        propagates instead of quietly losing to the incumbent."""
        from repro.samplers import autotune
        from repro.samplers.engine import IneligibleExecution

        real = autotune.measure_config
        err = IneligibleExecution if dropped else RuntimeError

        def measure(cfg, *a, **k):
            if cfg.chunk_steps == 16:
                raise err("refused")
            return real(cfg, *a, **k)

        monkeypatch.setattr(autotune, "measure_config", measure)
        target, init = _mh_setup(c=16)
        kw = dict(n_steps=32, repeats=1, chunk_candidates=(16,),
                  cache_path=str(tmp_path / "c.json"))
        cfg = samplers.EngineConfig(chunk_steps=32, execution="scan")
        if dropped:
            _, res = samplers.autotune_config(cfg, target, init, **kw)
            assert all(c[0] != 16 for c in res.candidates)
        else:
            with pytest.raises(RuntimeError, match="refused"):
                samplers.autotune_config(cfg, target, init, **kw)

    def test_cache_key_separates_shapes(self, tmp_path):
        target, init = _mh_setup(c=8)
        cfg = samplers.EngineConfig()
        k1 = samplers.autotune.tune_key(cfg, target, init)
        k2 = samplers.autotune.tune_key(cfg, target, init[:, :4])
        assert k1 != k2

    def test_tuned_stream_is_unchanged(self, tmp_path):
        """chunk_steps/execution tuning must never change the sample
        stream (what makes tuning safe across resume boundaries)."""
        target, init = _mh_setup()
        key = jax.random.PRNGKey(11)
        base = samplers.MHEngine(
            samplers.EngineConfig(chunk_steps=32, execution="scan")
        )
        tuned_engine, _ = samplers.autotune_engine(
            base, target, init, n_steps=32, repeats=1,
            chunk_candidates=(8,), cache_path=str(tmp_path / "c.json"),
        )
        a = base.run(key, target, 24, init)
        b = tuned_engine.run(key, target, 24, init)
        np.testing.assert_array_equal(
            np.asarray(a.samples), np.asarray(b.samples)
        )


class TestWorkloadPlanSurface:
    def test_workload_run_goes_through_plan(self):
        from repro import workloads

        k_init, k_run = jax.random.split(jax.random.PRNGKey(0))
        wl = workloads.build("ising", k_init, smoke=True, backend="scan")
        plan = wl.plan(k_run)
        assert isinstance(plan, samplers.RunPlan)
        res = wl.run(k_run)
        ref = wl.engine.submit(plan).result
        np.testing.assert_array_equal(
            np.asarray(res.samples), np.asarray(ref.samples)
        )

    def test_rate_key_names(self):
        from repro import workloads

        k = jax.random.PRNGKey(0)
        assert (
            workloads.build("ising", k, smoke=True).rate_key == "flip_rate"
        )
        assert (
            workloads.build("gmm", k, smoke=True).rate_key
            == "acceptance_rate"
        )
