"""MCMC engine correctness: proposal symmetry, stationarity, convergence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import metropolis, proposal, targets, uniform_rng
from repro.core.macro import CIMMacro, MacroConfig


class TestProposal:
    @given(
        nbits=st.integers(2, 6),
        p=st.floats(0.05, 0.5, exclude_max=False),
    )
    @settings(max_examples=25, deadline=None)
    def test_transfer_matrix_symmetric_doubly_stochastic(self, nbits, p):
        q = proposal.transfer_matrix(nbits, p)
        assert np.allclose(q, q.T), "q(i,j) == q(j,i) (paper Fig. 6)"
        assert np.allclose(q.sum(axis=1), 1.0, atol=1e-9)
        assert np.allclose(q.sum(axis=0), 1.0, atol=1e-9)

    def test_bitflip_rate(self):
        key = jax.random.PRNGKey(0)
        state = jnp.zeros(50_000, jnp.uint32)
        cand = proposal.propose_bitflip(key, state, 0.45, nbits=8)
        bits = np.unpackbits(
            np.asarray(cand, dtype=np.uint32).astype(">u4").view(np.uint8)
        )
        frac = bits.mean() * 4.0  # 8 of 32 bits are live
        assert frac == pytest.approx(0.45, abs=0.01)

    def test_hamming_popcount(self):
        x = np.array([0b1010, 0b1111])
        y = np.array([0b0000, 0b1110])
        assert list(proposal.hamming_distance(x, y)) == [2, 1]


class TestStationarity:
    def test_exact_transition_kernel_preserves_target(self):
        """P built from the bit-flip proposal + MH accept has p as its
        stationary distribution — the detailed-balance core of the paper."""
        rng = np.random.default_rng(0)
        nbits = 4
        logp = rng.normal(size=1 << nbits)
        p_target = np.exp(logp - logp.max())
        p_target /= p_target.sum()
        P = proposal.mh_transition_matrix(nbits, 0.45, np.log(p_target))
        assert np.allclose(P.sum(axis=1), 1.0, atol=1e-12)
        pi_next = p_target @ P
        assert np.allclose(pi_next, p_target, atol=1e-12)

    def test_detailed_balance(self):
        rng = np.random.default_rng(1)
        nbits = 3
        logp = rng.normal(size=1 << nbits)
        p_t = np.exp(logp)
        p_t /= p_t.sum()
        P = proposal.mh_transition_matrix(nbits, 0.4, np.log(p_t))
        flux = p_t[:, None] * P
        assert np.allclose(flux, flux.T, atol=1e-12)


class TestChainConvergence:
    def test_discrete_target_tv_distance(self):
        """Long chain matches an arbitrary 5-bit target within TV < 0.02."""
        rng = np.random.default_rng(2)
        nbits = 5
        logp_table = jnp.asarray(rng.normal(size=1 << nbits), jnp.float32)
        log_prob = targets.table_target(logp_table)
        cfg = metropolis.MHConfig(nbits=nbits, burn_in=500, rng_bit_width=16)
        res = metropolis.run_chain(
            jax.random.PRNGKey(3), log_prob, cfg, n_samples=2000, chain_shape=(64,)
        )
        counts = np.bincount(
            np.asarray(res.samples).reshape(-1), minlength=1 << nbits
        )
        emp = counts / counts.sum()
        ref = np.exp(np.asarray(logp_table, dtype=np.float64))
        ref /= ref.sum()
        tv = 0.5 * np.abs(emp - ref).sum()
        assert tv < 0.02, f"TV distance {tv}"

    def test_gmm_grid_sampling(self):
        """Paper Fig. 17(a) workload at reduced scale."""
        gmm = targets.GaussianMixture.paper_gmm()
        codec = targets.GridCodec(nbits=7, dim=1, lo=(-10.0,), hi=(10.0,))
        log_prob = targets.discretized_target(gmm, codec)
        cfg = metropolis.MHConfig(nbits=7, burn_in=500, rng_bit_width=16)
        res = metropolis.run_chain(
            jax.random.PRNGKey(4), log_prob, cfg, n_samples=1500, chain_shape=(64,)
        )
        counts = np.bincount(np.asarray(res.samples).reshape(-1), minlength=128)
        emp = counts / counts.sum()
        ref = targets.reference_grid_probs(gmm, codec)
        tv = 0.5 * np.abs(emp - ref).sum()
        assert tv < 0.03, f"GMM TV distance {tv}"

    def test_acceptance_rate_plausible(self):
        """§6.4: 'sampling accept ratio typically remains between 30% and
        40%' — our near-uniform proposal on a moderately peaked target
        lands in a broad sane band."""
        gmm = targets.GaussianMixture.paper_gmm()
        codec = targets.GridCodec(nbits=8, dim=1, lo=(-10.0,), hi=(10.0,))
        cfg = metropolis.MHConfig(nbits=8, burn_in=200)
        res = metropolis.run_chain(
            jax.random.PRNGKey(5),
            targets.discretized_target(gmm, codec),
            cfg,
            n_samples=500,
            chain_shape=(32,),
        )
        assert 0.1 < float(res.acceptance_rate) < 0.9


class TestUniformRNG:
    def test_uniform_range_and_mean(self):
        u = uniform_rng.uniform(jax.random.PRNGKey(6), (100_000,), 0.45)
        u = np.asarray(u)
        assert u.min() >= 0.0 and u.max() < 1.0
        assert u.mean() == pytest.approx(0.5, abs=0.005)

    def test_bit_uniformity_after_debias(self):
        words = uniform_rng.uniform_words(
            jax.random.PRNGKey(7), (200_000,), p_bfr=0.4, bit_width=8
        )
        w = np.asarray(words)
        for b in range(8):
            frac = ((w >> b) & 1).mean()
            assert frac == pytest.approx(0.5, abs=0.006), f"bit {b}"

    def test_biased_without_debias(self):
        """Sanity: raw pseudo-read bits ARE biased (the problem MSXOR fixes)."""
        from repro.core import bitcell

        raw = bitcell.pseudo_read_fresh(
            jax.random.PRNGKey(8), 0.4, shape=(100_000,)
        )
        assert float(raw.mean()) < 0.45


class TestMacro:
    def test_macro_sampling_with_stats(self):
        macro = CIMMacro(MacroConfig(nbits=8, burn_in=200))
        gmm = targets.GaussianMixture.paper_gmm()
        codec = targets.GridCodec(nbits=8, dim=1, lo=(-10.0,), hi=(10.0,))
        pts, stats = macro.sample_points(
            jax.random.PRNGKey(9), gmm, codec, n_samples=2000
        )
        assert pts.shape == (2000, 1)
        # 8-bit samples = 2 column groups; total energy must match the §6.4
        # model evaluated at the realised acceptance rate, charged for EVERY
        # chain step (burn-in included) but normalised by KEPT samples
        from repro.core import energy

        per_step_pj = energy.energy_per_sample_fj(stats.acceptance_rate, 8) / 1e3
        assert stats.energy_pj == pytest.approx(
            per_step_pj * stats.n_steps, rel=1e-3
        )
        assert stats.energy_per_sample_pj == pytest.approx(
            stats.energy_pj / stats.n_samples, rel=1e-6
        )
        assert stats.throughput_samples_per_s == pytest.approx(
            stats.n_samples / stats.modeled_time_s, rel=1e-6
        )
        assert stats.throughput_samples_per_s > 1e8  # 64 compartments
        assert 0.05 < stats.acceptance_rate < 0.95

    def test_macro_geometry_validation(self):
        with pytest.raises(ValueError):
            MacroConfig(nbits=128)
