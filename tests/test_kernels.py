"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles.

Kernels run in interpret mode on CPU (the TPU lowering is exercised by the
same pallas_call with interpret=False on device).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitcell
from repro.kernels.mh import ops as mh_ops
from repro.kernels.mh.ref import mh_chain_ref
from repro.kernels.msxor import ops as msxor_ops
from repro.kernels.msxor.ref import msxor_fold_ref, msxor_uniform_ref


class TestMSXORKernel:
    @pytest.mark.parametrize("n_stages", [1, 2, 3, 4])
    @pytest.mark.parametrize("m", [128, 500, 512, 1000, 4096])
    def test_fold_matches_ref(self, n_stages, m):
        key = jax.random.PRNGKey(n_stages * 1000 + m)
        raw = jax.random.bits(key, (1 << n_stages, m), dtype=jnp.uint32)
        out = msxor_ops.msxor_fold(raw, n_stages=n_stages)
        ref = msxor_fold_ref(raw, n_stages)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("m", [128, 777, 2048])
    def test_uniform_matches_ref(self, m):
        key = jax.random.PRNGKey(m)
        raw = jax.random.bits(key, (8, m), dtype=jnp.uint32)
        out = msxor_ops.msxor_uniform(raw)
        ref = msxor_uniform_ref(raw, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0)

    def test_uniform_values_in_range(self):
        raw = jax.random.bits(jax.random.PRNGKey(0), (8, 4096), dtype=jnp.uint32)
        u = np.asarray(msxor_ops.msxor_uniform(raw))
        assert u.min() >= 0.0 and u.max() < 1.0

    @given(st.integers(1, 4), st.integers(1, 300))
    @settings(max_examples=12, deadline=None)
    def test_fold_hypothesis_shapes(self, n_stages, m):
        key = jax.random.PRNGKey(m)
        raw = jax.random.bits(key, (1 << n_stages, m), dtype=jnp.uint32)
        out = msxor_ops.msxor_fold(raw, n_stages=n_stages)
        ref = msxor_fold_ref(raw, n_stages)
        assert out.shape == (m,)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_statistical_debias_property(self):
        """Kernel output bits are unbiased even from biased inputs."""
        raw = bitcell.raw_random_words(
            jax.random.PRNGKey(1), 0.4, (8, 100_000), nbits=32
        )
        out = np.asarray(msxor_ops.msxor_fold(raw))
        for b in range(0, 32, 5):
            frac = ((out >> b) & 1).mean()
            assert frac == pytest.approx(0.5, abs=0.01)


class TestMHKernel:
    @pytest.mark.parametrize(
        "b,v,c,k,nbits",
        [
            (1, 16, 64, 8, 4),
            (2, 256, 128, 32, 8),
            (3, 100, 256, 16, 7),   # non-power-of-two vocab
            (2, 1024, 300, 8, 10),  # padded chain axis
        ],
    )
    def test_fused_chain_matches_ref(self, b, v, c, k, nbits):
        key = jax.random.PRNGKey(b * 7 + v)
        table = jax.random.normal(key, (b, v), jnp.float32)
        init = jnp.broadcast_to(
            jnp.argmax(table, -1).astype(jnp.uint32)[:, None], (b, c)
        )
        rnd = mh_ops.generate_randomness(key, k, b, c, p_bfr=0.45)
        s_kernel, a_kernel = mh_ops.mh_sample(
            table, init, rnd.flips, rnd.u, nbits=nbits
        )
        s_ref, a_ref = mh_chain_ref(table, init, rnd.flips, rnd.u, nbits)
        np.testing.assert_array_equal(np.asarray(s_kernel), np.asarray(s_ref))
        np.testing.assert_array_equal(np.asarray(a_kernel), np.asarray(a_ref))

    def test_out_of_vocab_never_sampled(self):
        """V=100 < 2^7: out-of-support proposals must always be rejected."""
        key = jax.random.PRNGKey(42)
        table = jax.random.normal(key, (4, 100), jnp.float32)
        samples, _ = mh_ops.mh_sample_with_rng(key, table, n_steps=64, chains=32)
        assert int(np.asarray(samples).max()) < 100

    def test_kernel_distribution_matches_table(self):
        """Fused kernel chains converge to the softmax of the table."""
        key = jax.random.PRNGKey(7)
        logits = jnp.asarray(
            np.random.default_rng(0).normal(size=(1, 32)), jnp.float32
        )
        samples, accept = mh_ops.mh_sample_with_rng(
            key, logits, n_steps=400, chains=256
        )
        kept = np.asarray(samples[200:]).reshape(-1)
        emp = np.bincount(kept, minlength=32) / kept.size
        ref = np.asarray(jax.nn.softmax(logits[0]))
        tv = 0.5 * np.abs(emp - ref).sum()
        assert tv < 0.05, f"TV {tv}"

    def test_acceptance_counts_bounded(self):
        key = jax.random.PRNGKey(3)
        table = jax.random.normal(key, (2, 64), jnp.float32)
        _, accept = mh_ops.mh_sample_with_rng(key, table, n_steps=32, chains=16)
        a = np.asarray(accept)
        assert a.min() >= 0 and a.max() <= 32


class TestTokenSamplerFused:
    def test_serving_entry(self):
        key = jax.random.PRNGKey(11)
        logits = jax.random.normal(key, (8, 50), jnp.float32) * 3
        tokens, acc = mh_ops.sample_tokens_fused(key, logits, n_steps=64)
        assert tokens.shape == (8,)
        assert int(np.asarray(tokens).max()) < 50
        assert 0.0 <= float(acc) <= 1.0
