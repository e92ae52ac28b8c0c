"""Fused in-kernel randomness (DESIGN.md §Randomness): stream contract,
bit-parity, and statistical quality.

The contract under test:

  * the counter cipher (kernels/rng) matches the published
    Threefry-2x32-20 known-answer vectors, so the stream is pinned to a
    spec — not to whatever this repo happens to compute;
  * fused runs are **bit-identical** across the full
    {scan, pallas} x {mh, gibbs} x {chunked, monolithic} x step0 matrix
    — the pallas kernels make the draws in-kernel, the scan executor
    materialises them through ``FusedRandomness.chunk``, and both must
    land on the same uint32s;
  * chain c of a multi-chain fused run == a solo run with chain_id=c
    (the chain fold stays jax-side; kernels only ever see per-chain key
    words);
  * ``need_flips=False`` leaves the u stream bit-identical (operand
    salts separate the streams — no key split to diverge);
  * tempering swap draws ride the same backend protocol, so a 1-replica
    fused ladder degenerates to the plain fused run bit-for-bit;
  * slow marks: uniform/flip-plane statistics against the paper's
    <1e-5 bias budget (the conversion ``(bits >> 8) * 2^-24`` is exact,
    so the *analytic* bias is 0 — the empirical checks bound the
    CLT-sized sampling noise on top).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import samplers, tempering
from repro.kernels import rng
from repro.samplers import engine as engine_mod
from repro.serving import PackedExecutor, ServeRequest
from repro.workloads.ising import IsingModel
from repro.workloads.spin_glass import SpinGlass


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """The parity matrix compiles dozens of interpret-mode pallas
    programs; drop them from the process-wide executable cache on module
    exit so the rest of the suite doesn't compile on top of them."""
    yield
    jax.clear_caches()


def _engine(**kw):
    return samplers.MHEngine(
        samplers.EngineConfig(randomness="fused", **kw)
    )


def _mh_case(b=2, v=64, chains=8, seed=0):
    table = jax.random.normal(jax.random.PRNGKey(seed), (b, v), jnp.float32)
    init = jnp.broadcast_to(
        jnp.argmax(table, -1).astype(jnp.uint32)[:, None], (b, chains)
    )
    return samplers.TableTarget(table), init


def _gibbs_case(batch=2):
    model = IsingModel(height=4, width=6)
    return model, model.random_init(jax.random.PRNGKey(3), batch)


def _case(update):
    return _mh_case() if update == "mh" else _gibbs_case()


# The lattice targets the scan Gibbs step serves: a model, a model whose
# couplings are arrays, and a tempering-ladder replica of a model.
GIBBS_TARGETS = {
    "ising": lambda: IsingModel(height=4, width=6),
    "spin_glass": lambda: SpinGlass.bimodal(jax.random.PRNGKey(1), 4, 6),
    "tempered": lambda: tempering.Ladder((1.0, 0.6)).targets(
        IsingModel(height=4, width=6)
    )[1],
}


def _chunk_draw_reference(backend, key, target, init, n_steps, step0,
                          collect):
    """(samples, accept_count, final_words) of one chain the way the scan
    Gibbs executor ran before it drew in the step: the backend's chunk
    of uniforms for the whole run, then ``_gibbs_step`` once a step."""
    _, u = backend.chunk(key, step0, n_steps, init.shape, 1,
                         need_flips=False)
    parity = (step0 + jnp.arange(n_steps, dtype=jnp.int32)) % 2

    def body(c, x):
        c = engine_mod._gibbs_step(target, *c, *x)
        return c, c[0]

    carry = (init.astype(jnp.uint32), jnp.zeros(init.shape, jnp.int32))
    (final, acc), states = jax.lax.scan(body, carry, (u, parity))
    mode, k = engine_mod.parse_collect(collect)
    if mode == "thin":
        states = states[(-step0) % k::k]
    elif mode == "last":
        states = states[:0]
    return states, acc, final


class TestThreefryKnownAnswers:
    """Random123 test vectors for Threefry-2x32, 20 rounds."""

    def test_zero_key_zero_counter(self):
        x0, x1 = rng.threefry2x32(0, 0, 0, 0)
        assert (int(x0), int(x1)) == (0x6B200159, 0x99BA4EFE)

    def test_all_ones(self):
        ff = 0xFFFFFFFF
        x0, x1 = rng.threefry2x32(ff, ff, ff, ff)
        assert (int(x0), int(x1)) == (0x1CB996FC, 0xBB002BE7)

    def test_pi_digits(self):
        x0, x1 = rng.threefry2x32(
            0x13198A2E, 0x03707344, 0x243F6A88, 0x85A308D3
        )
        assert (int(x0), int(x1)) == (0xC4923A9C, 0x483DF7A0)

    def test_uniform_conversion_range_and_exactness(self):
        u = rng.uniform_at(jnp.uint32(1), jnp.uint32(2), rng.site_index((4096,)))
        u = np.asarray(u)
        assert u.min() >= 0.0 and u.max() < 1.0
        # every value is a multiple of 2^-24 — float32-exact by design
        np.testing.assert_array_equal(u * (1 << 24), np.round(u * (1 << 24)))


class TestFusedParityMatrix:
    """The ISSUE-6 acceptance matrix: one fused stream per key, whatever
    the executor, the chunking, or the stream offset."""

    @pytest.mark.parametrize("update", ["mh", "gibbs"])
    @pytest.mark.parametrize("chunk", [7, 1000])
    @pytest.mark.parametrize("step0", [0, 7])
    def test_scan_pallas_bit_identical(self, update, chunk, step0):
        target, init = _case(update)
        key = jax.random.PRNGKey(11)
        runs = {}
        for execution in ("scan", "pallas"):
            engine = _engine(
                update=update, execution=execution, chunk_steps=chunk
            )
            runs[execution] = engine.run(key, target, 20, init, step0=step0)
        for field in ("samples", "accept_count", "final_words", "final_logp"):
            np.testing.assert_array_equal(
                np.asarray(getattr(runs["scan"], field)),
                np.asarray(getattr(runs["pallas"], field)),
            )

    @pytest.mark.parametrize("update", ["mh", "gibbs"])
    def test_chunked_equals_monolithic(self, update):
        target, init = _case(update)
        key = jax.random.PRNGKey(5)
        mono = _engine(update=update, chunk_steps=1000).run(
            key, target, 23, init
        )
        chunked = _engine(update=update, chunk_steps=6).run(
            key, target, 23, init
        )
        np.testing.assert_array_equal(
            np.asarray(mono.samples), np.asarray(chunked.samples)
        )

    @pytest.mark.parametrize("update", ["mh", "gibbs"])
    @pytest.mark.parametrize("execution", ["scan", "pallas"])
    def test_multichain_matches_solo(self, update, execution):
        target, init = _case(update)
        key = jax.random.PRNGKey(9)
        multi = _engine(
            update=update, execution=execution, num_chains=3
        ).run(key, target, 12, jnp.broadcast_to(init, (3, *init.shape)))
        solo = _engine(update=update, execution=execution)
        for c in range(3):
            r = solo.run(key, target, 12, init, chain_id=c)
            np.testing.assert_array_equal(
                np.asarray(multi.samples[c]), np.asarray(r.samples)
            )

    @pytest.mark.parametrize("target_name", sorted(GIBBS_TARGETS))
    @pytest.mark.parametrize("step0", [6, 9])
    @pytest.mark.parametrize("collect", ["all", "thin:3", "last"])
    @pytest.mark.parametrize("num_chains", [1, 4])
    def test_in_step_draw_matches_chunk_draw(
        self, target_name, step0, collect, num_chains
    ):
        """The scan Gibbs step draws its fused uniforms itself; every
        result stays the chunk draw's, bit for bit (13 steps in chunks
        of 4, so a remainder chunk runs too)."""
        target = GIBBS_TARGETS[target_name]()
        inits = [target.random_init(jax.random.PRNGKey(3 + c), 2)
                 for c in range(num_chains)]
        key = jax.random.PRNGKey(13)
        engine = _engine(update="gibbs", execution="scan", chunk_steps=4,
                         num_chains=num_chains)
        init = inits[0] if num_chains == 1 else jnp.stack(inits)
        out = engine.run(key, target, 13, init, step0=step0, collect=collect)
        for c in range(num_chains):
            samples, acc, final = _chunk_draw_reference(
                engine.randomness, samplers.chain_key(key, c), target,
                inits[c], 13, step0, collect,
            )
            pick = (lambda x: x) if num_chains == 1 else (lambda x: x[c])
            np.testing.assert_array_equal(pick(out.samples), samples)
            np.testing.assert_array_equal(pick(out.accept_count), acc)
            np.testing.assert_array_equal(pick(out.final_words), final)
            np.testing.assert_array_equal(
                pick(out.final_logp), engine_mod._gibbs_logp(target, final)
            )

    @pytest.mark.parametrize("randomness", ["host", "cim"])
    def test_host_and_cim_gibbs_keep_their_stream(self, randomness):
        """Host and cim draw in the scan Gibbs step too: a step's draw
        depends only on (key, t, shape), so their runs stream exactly
        what the chunk draw gave them."""
        target, init = _gibbs_case()
        key = jax.random.PRNGKey(21)
        engine = samplers.MHEngine(samplers.EngineConfig(
            update="gibbs", randomness=randomness, execution="scan",
            chunk_steps=4,
        ))
        out = engine.run(key, target, 11, init, step0=5)
        samples, acc, final = _chunk_draw_reference(
            engine.randomness, samplers.chain_key(key, 0), target, init, 11,
            5, "all",
        )
        np.testing.assert_array_equal(out.samples, samples)
        np.testing.assert_array_equal(out.accept_count, acc)
        np.testing.assert_array_equal(out.final_words, final)

    @pytest.mark.parametrize("randomness", ["host", "cim", "fused"])
    @pytest.mark.parametrize("need_flips", [True, False])
    def test_chunk_is_step_stacked(self, randomness, need_flips):
        """Every backend's chunk over [start, start + n) holds exactly
        the operands its ``step`` draws at each absolute step, which is
        what lets a scan step draw its own."""
        backend = samplers.make_randomness_backend(randomness, p_bfr=0.3)
        key, shape = jax.random.PRNGKey(8), (2, 3, 5)
        flips, u = backend.chunk(key, 7, 4, shape, 6, need_flips=need_flips)
        for i in range(4):
            f_t, u_t = backend.step(key, 7 + i, shape, 6,
                                    need_flips=need_flips)
            np.testing.assert_array_equal(u[i], u_t)
            if need_flips:
                np.testing.assert_array_equal(flips[i], f_t)
            else:
                assert flips is None and f_t is None

    def test_serving_slots_at_both_parities(self):
        """Two served requests a segment apart (3 steps, so one slot
        sits at an odd step when the other joins at step 0) each stream
        the chunk draw's result through the vmapped class program."""
        ex = PackedExecutor.for_workload(
            "ising", n_slots=2, randomness="fused", execution="scan",
            smoke=True, chunk_steps=3,
        )
        a = ServeRequest(rid=0, workload="ising", n_steps=12, seed=5,
                         collect="all")
        ex.admit(a)
        ex.advance_chunk()
        b = ServeRequest(rid=1, workload="ising", n_steps=9, seed=6,
                         collect="all")
        ex.admit(b)
        while ex.active_count:
            ex.advance_chunk()
        ex.drain()
        for req in (a, b):
            init, k_run, _ = ex.request_init(req)
            samples, acc, final = _chunk_draw_reference(
                ex.engine.randomness, samplers.chain_key(k_run, 0),
                ex.target, init, req.n_steps, 0, "all",
            )
            np.testing.assert_array_equal(req.samples, samples)
            np.testing.assert_array_equal(req.accept_count, acc)
            np.testing.assert_array_equal(req.final_words, final)

    def test_fused_distinct_from_host_and_cim(self):
        target, init = _mh_case()
        key = jax.random.PRNGKey(2)
        out = {
            name: samplers.MHEngine(
                samplers.EngineConfig(randomness=name)
            ).run(key, target, 16, init).samples
            for name in ("host", "cim", "fused")
        }
        assert not np.array_equal(np.asarray(out["fused"]), np.asarray(out["host"]))
        assert not np.array_equal(np.asarray(out["fused"]), np.asarray(out["cim"]))


class TestFusedBackendProtocol:
    def test_need_flips_false_same_u(self):
        backend = samplers.FusedRandomness(p_bfr=0.45)
        key = jax.random.PRNGKey(4)
        flips, u_full = backend.chunk(key, 3, 5, (2, 7), nbits=6)
        none, u_lean = backend.chunk(
            key, 3, 5, (2, 7), nbits=6, need_flips=False
        )
        assert none is None
        assert flips.dtype == jnp.uint32
        np.testing.assert_array_equal(np.asarray(u_full), np.asarray(u_lean))

    def test_chunk_concatenation_is_stream_slice(self):
        backend = samplers.FusedRandomness()
        key = jax.random.PRNGKey(8)
        _, u_all = backend.chunk(key, 0, 10, (3,), nbits=4)
        _, u_a = backend.chunk(key, 0, 4, (3,), nbits=4)
        _, u_b = backend.chunk(key, 4, 6, (3,), nbits=4)
        np.testing.assert_array_equal(
            np.asarray(u_all), np.concatenate([u_a, u_b])
        )

    def test_make_backend_dispatch(self):
        backend = samplers.make_randomness_backend("fused", p_bfr=0.3)
        assert isinstance(backend, samplers.FusedRandomness)
        assert backend.name == "fused"
        with pytest.raises(ValueError, match="host|cim|fused"):
            samplers.make_randomness_backend("hw", p_bfr=0.3)

    def test_one_replica_tempered_ladder_degenerates(self):
        model = SpinGlass.bimodal(jax.random.PRNGKey(1), 4, 4)
        init = model.random_init(jax.random.PRNGKey(2), 2)
        key = jax.random.PRNGKey(3)
        engine = _engine(update="gibbs", chunk_steps=8)
        rex = tempering.ReplicaExchange(
            ladder=tempering.Ladder((1.0,)), engine=engine, swap_every=7
        )
        tempered = rex.run(key, model, 25, init[None])
        plain = engine.run(key, model, 25, init)
        np.testing.assert_array_equal(
            np.asarray(tempered.samples[0]), np.asarray(plain.samples)
        )


class TestFusedStreamStatistics:
    """Empirical quality of the cipher stream against the paper's <1e-5
    uniformity budget: the fused conversion is analytically unbiased, so
    the checks bound CLT sampling noise around the exact targets."""

    N = 1 << 21  # draws per check; CLT sigma for a bit mean is ~3.5e-4

    def _uniforms(self, seed=0):
        k0, k1 = rng.key_words(jax.random.PRNGKey(seed))
        s0, s1 = rng.step_key(k0, k1, jnp.uint32(0))
        return np.asarray(rng.uniform_at(s0, s1, rng.site_index((self.N,))))

    @pytest.mark.slow
    def test_uniform_mean_and_ks(self):
        u = self._uniforms()
        # mean: exact target 0.5 - 2^-25 (midpoint of the 2^24 grid)
        assert abs(u.mean() - 0.5) < 5 * (1 / np.sqrt(12 * self.N))
        from scipy import stats

        d, p = stats.kstest(u, "uniform")
        assert p > 1e-4, f"KS rejects uniformity: D={d}, p={p}"

    @pytest.mark.slow
    def test_flip_plane_frequencies(self):
        p_bfr = 0.45
        k0, k1 = rng.key_words(jax.random.PRNGKey(1))
        s0, s1 = rng.step_key(k0, k1, jnp.uint32(0))
        words = np.asarray(
            rng.flips_at(
                s0, s1, rng.site_index((self.N,)), 8,
                rng.threshold_u32(p_bfr),
            )
        )
        # threshold_u32 quantises p to 2^-32 — bias < 1e-5 by construction
        assert abs(rng.threshold_u32(p_bfr) / 2**32 - p_bfr) < 1e-5
        sigma = np.sqrt(p_bfr * (1 - p_bfr) / self.N)
        for b in range(8):
            freq = ((words >> b) & 1).mean()
            assert abs(freq - p_bfr) < 5 * sigma, f"plane {b}: {freq}"

    @pytest.mark.slow
    def test_uniform_bit_planes_unbiased(self):
        u = self._uniforms(seed=2)
        bits = (u * (1 << 24)).astype(np.uint32)
        sigma = 0.5 / np.sqrt(self.N)
        for b in range(24):
            freq = ((bits >> b) & 1).mean()
            assert abs(freq - 0.5) < 5 * sigma, f"bit {b}: {freq}"

    @pytest.mark.slow
    @given(st.integers(0, 2**31 - 1), st.integers(0, 1000))
    @settings(max_examples=10, deadline=None)
    def test_any_key_and_step_stays_uniform(self, seed, step):
        k0, k1 = rng.key_words(jax.random.PRNGKey(seed))
        s0, s1 = rng.step_key(k0, k1, jnp.uint32(step))
        u = np.asarray(
            rng.uniform_at(s0, s1, rng.site_index((1 << 16,)))
        )
        assert u.min() >= 0.0 and u.max() < 1.0
        assert abs(u.mean() - 0.5) < 5 / np.sqrt(12 * (1 << 16))

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_salts_decorrelate_streams(self, seed):
        """The u draw and every flip plane use distinct salts — no site's
        uniform can be reconstructed from its flip word."""
        k0, k1 = rng.key_words(jax.random.PRNGKey(seed))
        s0, s1 = rng.step_key(k0, k1, jnp.uint32(0))
        site = rng.site_index((256,))
        u_bits = np.asarray(rng.raw_draw(s0, s1, site, rng.U_SALT))
        f_bits = np.asarray(rng.raw_draw(s0, s1, site, rng.FLIP_SALT))
        assert not np.array_equal(u_bits, f_bits)
