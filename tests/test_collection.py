"""Collection axis (DESIGN.md §Collection): kept-set parity + operand-lean u.

The axis must never change the chain — only how much of it leaves the
engine:

  * ``thin:k`` == the strided slice ``all[(-step0) % k :: k]`` bit for
    bit, on every executor x update-rule x randomness combination,
  * ``last`` reproduces ``all``'s (final_words, final_logp,
    accept_count) exactly while emitting a (0, *chain) sample stream,
  * ``need_flips=False`` (the u-only operand path the Gibbs executors
    and the tempering swap test use) leaves the u stream bit-identical,
  * the kept set is defined on *absolute* steps, so thinning commutes
    with chunking and with ``step0`` segmentation (the tempering
    segment contract).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers, workloads
from repro.samplers.randomness import draw_cache_size
from repro.workloads.ising import IsingModel


def _mh_case(chains=16, v=64):
    key = jax.random.PRNGKey(2)
    table = jax.random.normal(key, (2, v), jnp.float32)
    target = samplers.TableTarget(table)
    init = jnp.broadcast_to(
        jnp.argmax(table, -1).astype(jnp.uint32)[:, None], (2, chains)
    )
    return target, init


def _gibbs_case(batch=2):
    model = IsingModel(height=4, width=6)
    return model, model.random_init(jax.random.PRNGKey(3), batch)


def _engine(update, execution, randomness, **kw):
    return samplers.MHEngine(
        samplers.EngineConfig(
            update=update, execution=execution, randomness=randomness, **kw
        )
    )


def _case(update):
    return _mh_case() if update == "mh" else _gibbs_case()


class TestKeptSetParity:
    """thin == strided slice of all; last == all's final carry — across
    the full {scan, pallas} x {mh, gibbs} x {host, cim} matrix."""

    @pytest.mark.parametrize("update", ["mh", "gibbs"])
    @pytest.mark.parametrize("execution", ["scan", "pallas"])
    @pytest.mark.parametrize("randomness", ["host", "cim"])
    def test_modes_against_all(self, update, execution, randomness):
        target, init = _case(update)
        engine = _engine(update, execution, randomness, chunk_steps=7)
        key = jax.random.PRNGKey(11)
        r_all = engine.run(key, target, 40, init)
        r_thin = engine.run(key, target, 40, init, collect="thin:6")
        r_last = engine.run(key, target, 40, init, collect="last")
        np.testing.assert_array_equal(
            np.asarray(r_thin.samples), np.asarray(r_all.samples)[0::6]
        )
        assert r_last.samples.shape == (0, *init.shape)
        for field in ("final_words", "final_logp", "accept_count"):
            for r in (r_thin, r_last):
                np.testing.assert_array_equal(
                    np.asarray(getattr(r, field)),
                    np.asarray(getattr(r_all, field)),
                )

    def test_thin_one_is_all(self):
        target, init = _mh_case()
        engine = _engine("mh", "scan", "cim", chunk_steps=8)
        key = jax.random.PRNGKey(5)
        r_all = engine.run(key, target, 20, init)
        r_thin = engine.run(key, target, 20, init, collect="thin:1")
        np.testing.assert_array_equal(
            np.asarray(r_thin.samples), np.asarray(r_all.samples)
        )

    @pytest.mark.parametrize("update", ["mh", "gibbs"])
    def test_thin_respects_step0_offset(self, update):
        """The kept set is {t : (step0 + t) % k == 0}: a segment resumed
        at step0 = s keeps exactly the monolithic kept rows that fall in
        the segment, so segmented thin == thinned monolithic."""
        target, init = _case(update)
        engine = _engine(update, "scan", "host", chunk_steps=5)
        key = jax.random.PRNGKey(9)
        k = 4
        mono = engine.run(key, target, 26, init, collect=f"thin:{k}")
        head = engine.run(key, target, 11, init, collect=f"thin:{k}")
        tail = engine.run(
            key, target, 15, head.final_words, step0=11, collect=f"thin:{k}"
        )
        assert head.samples.shape[0] == samplers.kept_count(11, k, 0)
        assert tail.samples.shape[0] == samplers.kept_count(15, k, 11)
        np.testing.assert_array_equal(
            np.asarray(mono.samples),
            np.concatenate(
                [np.asarray(head.samples), np.asarray(tail.samples)]
            ),
        )


class TestCollectEdges:
    """The chunk-schedule edges the axis creates."""

    @pytest.mark.parametrize("chunk_steps", [1, 1000])
    def test_extreme_chunking_is_invariant(self, chunk_steps):
        """chunk_steps = 1 and chunk_steps > n_steps both reproduce the
        default-chunk stream for every collection mode."""
        target, init = _gibbs_case()
        key = jax.random.PRNGKey(13)
        ref = _engine("gibbs", "scan", "cim", chunk_steps=8)
        got = _engine("gibbs", "scan", "cim", chunk_steps=chunk_steps)
        for collect in ("all", "thin:6", "last"):
            r_ref = ref.run(key, target, 22, init, collect=collect)
            r_got = got.run(key, target, 22, init, collect=collect)
            np.testing.assert_array_equal(
                np.asarray(r_ref.samples), np.asarray(r_got.samples)
            )
            np.testing.assert_array_equal(
                np.asarray(r_ref.final_words), np.asarray(r_got.final_words)
            )

    @pytest.mark.parametrize("execution", ["scan", "pallas"])
    def test_thin_k_beyond_n_steps(self, execution):
        """k > n_steps keeps exactly the t = 0 row (step0 = 0)."""
        target, init = _mh_case()
        engine = _engine("mh", execution, "host", chunk_steps=4)
        key = jax.random.PRNGKey(17)
        r_all = engine.run(key, target, 10, init)
        r_thin = engine.run(key, target, 10, init, collect="thin:1000")
        assert r_thin.samples.shape[0] == 1
        np.testing.assert_array_equal(
            np.asarray(r_thin.samples), np.asarray(r_all.samples)[:1]
        )
        # ... and an offset that pushes the single kept row out of range
        r_none = engine.run(
            key, target, 10, init, step0=4, collect="thin:1000"
        )
        assert r_none.samples.shape[0] == 0

    @pytest.mark.parametrize("update,execution", [
        ("mh", "scan"), ("mh", "pallas"),
        ("gibbs", "scan"), ("gibbs", "pallas"),
    ])
    def test_last_multi_chain_segmented_resume(self, update, execution):
        """collect="last" under num_chains > 1: a step0-segmented pair of
        runs carries exactly the monolithic final state, per chain."""
        target, init = _case(update)
        num_chains = 3
        cinit = jnp.broadcast_to(init, (num_chains, *init.shape))
        engine = _engine(
            update, execution, "cim", chunk_steps=5, num_chains=num_chains
        )
        key = jax.random.PRNGKey(19)
        mono = engine.run(key, target, 24, cinit, collect="last")
        head = engine.run(key, target, 11, cinit, collect="last")
        tail = engine.run(
            key, target, 13, head.final_words, step0=11, collect="last"
        )
        assert mono.samples.shape == (num_chains, 0, *init.shape)
        np.testing.assert_array_equal(
            np.asarray(tail.final_words), np.asarray(mono.final_words)
        )
        np.testing.assert_array_equal(
            np.asarray(head.accept_count + tail.accept_count),
            np.asarray(mono.accept_count),
        )

    def test_thin_requires_concrete_step0(self):
        """The kept count is part of the output shape, so scan execution
        rejects a traced step0 under thin (all/last accept it)."""
        target, init = _mh_case()
        engine = _engine("mh", "scan", "host")
        key = jax.random.PRNGKey(23)

        def thin_run(s):
            return engine.run(
                key, target, 8, init, step0=s, collect="thin:2"
            ).final_words

        with pytest.raises(ValueError, match="concrete"):
            jax.jit(thin_run)(jnp.int32(3))
        # the "last" carry stays traceable — the tempering segment path
        last_run = jax.jit(
            lambda s: engine.run(
                key, target, 8, init, step0=s, collect="last"
            ).final_words
        )
        eager = engine.run(key, target, 8, init, step0=3, collect="last")
        np.testing.assert_array_equal(
            np.asarray(last_run(jnp.int32(3))),
            np.asarray(eager.final_words),
        )

    @pytest.mark.parametrize("update,randomness", [
        ("mh", "cim"), ("mh", "fused"),
        ("gibbs", "cim"), ("gibbs", "fused"),
    ])
    def test_pallas_accepts_traced_step0(self, update, randomness):
        """Pallas executors take step0 as a runtime value (the fused
        kernels as a per-slot operand), so a traced step0 works under
        all/last — the serving tier's packed segments jit over it."""
        target, init = _case(update)
        engine = _engine(update, "pallas", randomness)
        key = jax.random.PRNGKey(31)

        traced = jax.jit(
            lambda s: engine.run(
                key, target, 8, init, step0=s, collect="all"
            ).samples
        )
        eager = engine.run(key, target, 8, init, step0=5, collect="all")
        np.testing.assert_array_equal(
            np.asarray(traced(jnp.int32(5))), np.asarray(eager.samples)
        )


class TestOperandLeanRandomness:
    @pytest.mark.parametrize("name", ["host", "cim"])
    def test_u_stream_invariant_without_flips(self, name):
        """need_flips=False skips flip planes and leaves u bit-identical
        (the step key splits before either operand is drawn)."""
        backend = samplers.make_randomness_backend(name, p_bfr=0.45)
        key = jax.random.PRNGKey(29)
        flips, u_ref = backend.chunk(key, 3, 6, (2, 5), 4)
        none_flips, u_lean = backend.chunk(
            key, 3, 6, (2, 5), 4, need_flips=False
        )
        assert flips is not None and none_flips is None
        np.testing.assert_array_equal(np.asarray(u_ref), np.asarray(u_lean))


class TestCollectValidation:
    @pytest.mark.parametrize("bad", ["thin:0", "thin:-2", "thin:x", "median"])
    def test_bad_specs_rejected(self, bad):
        with pytest.raises(ValueError, match="collect"):
            samplers.EngineConfig(collect=bad)

    def test_kept_count(self):
        assert samplers.kept_count(10, 1) == 10
        assert samplers.kept_count(10, 3) == 4          # t = 0, 3, 6, 9
        assert samplers.kept_count(10, 3, step0=1) == 3  # t = 2, 5, 8
        assert samplers.kept_count(10, 1000) == 1
        assert samplers.kept_count(10, 1000, step0=4) == 0


class TestWorkloadAndTemperingWiring:
    def test_workload_diagnostics_under_thin_and_last(self):
        key = jax.random.PRNGKey(0)
        k_init, k_run = jax.random.split(key)
        thin = workloads.build("ising", k_init, smoke=True, collect="thin:4")
        r = thin.run(k_run)
        assert r.samples.shape[0] == samplers.kept_count(thin.n_steps, 4)
        diag = thin.diagnostics(r)
        assert diag["n_steps"] == r.samples.shape[0] - thin.kept_burn_in()
        assert "flip_rate" in diag and "tau" in diag
        last = workloads.build("ising", k_init, smoke=True, collect="last")
        r = last.run(k_run)
        assert r.samples.shape[0] == 0
        diag = last.diagnostics(r)
        assert set(diag) == {"n_steps", "flip_rate"}

    def test_tempered_streams_inherit_collection(self):
        """Replica exchange's segments resume on absolute steps, so an
        engine with collect="thin:k" yields exactly the thinned tempered
        stream, and collect="last" the same final states."""
        from repro import tempering

        model, init = _gibbs_case(batch=1)
        rinit = jnp.broadcast_to(init, (2, *init.shape))
        key = jax.random.PRNGKey(31)
        ladder = tempering.Ladder.geometric(2, beta_min=0.5)

        def run(collect):
            engine = _engine("gibbs", "scan", "cim", chunk_steps=5,
                             collect=collect)
            rex = tempering.ReplicaExchange(
                ladder=ladder, engine=engine, swap_every=8
            )
            return rex.run(key, model, 24, rinit)

        r_all, r_thin, r_last = run("all"), run("thin:4"), run("last")
        np.testing.assert_array_equal(
            np.asarray(r_thin.samples), np.asarray(r_all.samples)[:, 0::4]
        )
        assert r_last.samples.shape == (2, 0, *init.shape)
        for r in (r_thin, r_last):
            np.testing.assert_array_equal(
                np.asarray(r.final_words), np.asarray(r_all.final_words)
            )


def _per_step_draw(name, key, start, n_steps, shape, nbits, need_flips):
    """The (flips, u) stream rebuilt step by step with no jit: the
    per-step ``fold_in(key, t)``, the ``(k_flip, k_u)`` split, then the
    backend's own draw of each operand."""
    from repro.core import bitcell, uniform_rng

    flips, us = [], []
    with jax.disable_jit():
        for t in range(start, start + n_steps):
            k_flip, k_u = jax.random.split(jax.random.fold_in(key, t))
            if name == "host":
                us.append(jax.random.uniform(k_u, shape, jnp.float32))
                planes = jax.random.bernoulli(k_flip, 0.45, (*shape, nbits))
                flips.append(
                    np.sum(
                        np.asarray(planes, np.uint32)
                        << np.arange(nbits, dtype=np.uint32),
                        axis=-1, dtype=np.uint32,
                    )
                )
            else:
                us.append(uniform_rng.uniform(k_u, shape, 0.45, 16, 3))
                flips.append(
                    bitcell.raw_random_words(k_flip, 0.45, shape, nbits)
                )
    u = np.stack([np.asarray(x) for x in us])
    if not need_flips:
        return None, u
    return np.stack([np.asarray(x) for x in flips]), u


class TestCompiledDraw:
    """``RandomnessBackend.chunk`` is one compiled program per ``(backend,
    n_steps, shape, nbits, need_flips)`` and draws the same stream as
    the per-step body, word for word."""

    @pytest.mark.parametrize("need_flips", [True, False])
    @pytest.mark.parametrize("name", ["host", "cim"])
    @pytest.mark.parametrize("start_kind", ["python", "traced"])
    def test_chunk_equals_per_step_draw(self, name, need_flips, start_kind):
        backend = samplers.make_randomness_backend(name, p_bfr=0.45)
        key = jax.random.PRNGKey(41)
        shape, nbits, start, n = (2, 5), 6, 9, 4
        if start_kind == "python":
            flips, u = backend.chunk(key, start, n, shape, nbits, need_flips)
        else:
            flips, u = jax.jit(
                lambda s: backend.chunk(key, s, n, shape, nbits, need_flips)
            )(jnp.int32(start))
        ref_flips, ref_u = _per_step_draw(
            name, key, start, n, shape, nbits, need_flips
        )
        assert u.dtype == jnp.float32 and u.shape == (n, *shape)
        np.testing.assert_array_equal(np.asarray(u), ref_u)
        if need_flips:
            assert flips.dtype == jnp.uint32
            np.testing.assert_array_equal(np.asarray(flips), ref_flips)
        else:
            assert flips is None

    @pytest.mark.parametrize(
        "name,width", [("host", 11), ("cim", 12), ("fused", 13)]
    )
    def test_chunks_at_other_starts_share_one_program(self, name, width):
        """The key and ``start`` are traced: two chunks of equal length
        at different starts compile once.  (``width`` keeps each case's
        signature new to the process-wide cache.)"""
        backend = samplers.make_randomness_backend(name, p_bfr=0.45)
        key = jax.random.PRNGKey(43)
        before = draw_cache_size()
        a = backend.chunk(key, 0, 3, (1, width), 4)
        b = backend.chunk(jax.random.PRNGKey(44), 96, 3, (1, width), 4)
        assert draw_cache_size() == before + 1
        assert not np.array_equal(np.asarray(a[1]), np.asarray(b[1]))

    @pytest.mark.parametrize("randomness", ["host", "cim"])
    @pytest.mark.parametrize("update", ["mh", "gibbs"])
    def test_pallas_chunked_equals_monolithic(self, update, randomness):
        """The eager Pallas executors draw each chunk through the
        compiled program; chunked and monolithic runs agree bit for bit
        (interpret mode off the chip)."""
        if update == "gibbs":
            target, init = _gibbs_case()
        else:
            target, init = _mh_case(chains=8)
        key = jax.random.PRNGKey(47)

        def run(chunk):
            engine = samplers.MHEngine(
                samplers.EngineConfig(
                    update=update, randomness=randomness,
                    execution="pallas", chunk_steps=chunk,
                )
            )
            return engine.run(key, target, 12, init, collect="all")

        chunked, mono = run(5), run(64)
        for field in ("samples", "accept_count", "final_words"):
            np.testing.assert_array_equal(
                np.asarray(getattr(chunked, field)),
                np.asarray(getattr(mono, field)),
            )
