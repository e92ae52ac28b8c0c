"""Decode-position token sampling at a width past one lookup window.

The engine's ``sample_tokens`` against a plain reference written from
the definitions on seeded Zipf-ranked logits, B = 16 rows over V =
3,001 ids (not a power of two, wider than one 128-entry window, so the
MH kernels take their window lookup): tokens, final words and accept
counts equal bit for bit under {scan, pallas-interpret} x {fused, cim};
words at or past V are never accepted; the ``tokens`` workload, its
CLI smoke, and the decode step's span and counters.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers, telemetry, workloads
from repro.kernels.mh import mh as mh_kernel
from repro.launch import sample as sample_cli
from repro.samplers import engine as engine_mod
from repro.workloads import tokens

B, V, K = 16, 3001, 64
NBITS = math.ceil(math.log2(V))
P_BFR = 0.45
U_SALT, FLIP_SALT = 0x554E4946, 0x464C4950
ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


# --- plain reference ----------------------------------------------------------


def _threefry(k0, k1, x0, x1):
    """Threefry-2x32-20 (Salmon et al., SC'11) over uint32 arrays."""
    k0, k1, x0, x1 = (jnp.asarray(a, jnp.uint32) for a in (k0, k1, x0, x1))
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(0x1BD11BDA))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for i in range(5):
        for r in ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << jnp.uint32(r)) | (x1 >> jnp.uint32(32 - r))
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def _fused_operands(chain, t):
    """Flip words and uniforms of step t at sites 0..B-1 (one chain a
    row): the fused stream contract."""
    words = jax.random.key_data(chain)
    s0, s1 = _threefry(words[0], words[1], jnp.uint32(t), 0)
    site = jnp.arange(B, dtype=jnp.uint32)
    bits = _threefry(s0, s1, site, U_SALT)[0]
    u = (bits >> 8).astype(jnp.int32).astype(jnp.float32) / (1 << 24)
    threshold = jnp.uint32(round(P_BFR * 2**32))
    flip = jnp.zeros((B,), jnp.uint32)
    for i in range(NBITS):
        plane = _threefry(s0, s1, site, FLIP_SALT + i)[0] < threshold
        flip = flip | (plane.astype(jnp.uint32) << i)
    return flip, u


def _pack(bits, n):
    weights = jnp.uint32(1) << jnp.arange(n, dtype=jnp.uint32)
    return jnp.sum(bits.astype(jnp.uint32) * weights, axis=-1).astype(
        jnp.uint32)


def _cim_operands(chain, t):
    """Pseudo-read flip planes and MSXOR uniforms of step t (the engine's
    cim defaults: 16-bit uniforms, 3 stages, raw bias p_bfr)."""
    k_flip, k_u = jax.random.split(jax.random.fold_in(chain, t))
    flip = _pack(jax.random.bernoulli(k_flip, P_BFR, (B, 1, NBITS)), NBITS)
    raw = jax.random.bernoulli(k_u, P_BFR, (B, 1, 8, 16))
    bits = jax.lax.reduce(raw, False, jnp.logical_xor, (2,))
    return flip[:, 0], _pack(bits, 16)[:, 0].astype(jnp.float32) / 2**16


def reference(key, logits, randomness):
    """Tokens, final words and accept counts: one float32 MH chain per
    row, started at the row argmax, proposals XOR flip words, -inf at or
    past V."""
    table = jnp.asarray(logits, jnp.float32)
    chain = jax.random.fold_in(key, 0)
    operands = _fused_operands if randomness == "fused" else _cim_operands

    def log_prob(w):
        idx = jnp.minimum(w, V - 1).astype(jnp.int32)
        vals = jnp.take_along_axis(table, idx[:, None], axis=1)[:, 0]
        return jnp.where(w < V, vals, -jnp.inf)

    w = jnp.argmax(table, axis=1).astype(jnp.uint32)
    lp, acc, proposed_out = log_prob(w), jnp.zeros((B,), jnp.int32), 0
    for t in range(K):
        flip, u = operands(chain, t)
        cand = w ^ (flip & jnp.uint32((1 << NBITS) - 1))
        lp_c = log_prob(cand)
        ok = (u < jnp.exp(jnp.minimum(lp_c - lp, 0.0))) & jnp.isfinite(lp_c)
        proposed_out += int(jnp.sum(cand >= V))
        w, lp = jnp.where(ok, cand, w), jnp.where(ok, lp_c, lp)
        acc = acc + ok.astype(jnp.int32)
    return {"tokens": np.asarray(w, np.int32), "final_words": np.asarray(w),
            "accept_count": np.asarray(acc), "proposed_out": proposed_out}


@pytest.fixture(scope="module")
def logits():
    return tokens.table_logits(jax.random.PRNGKey(11), 0, B, V)


def _engine(randomness, execution, collect="last"):
    return samplers.MHEngine(samplers.EngineConfig(
        randomness=randomness, execution=execution, chunk_steps=32,
        collect=collect,
    ))


# --- the engine against the reference ----------------------------------------


@pytest.mark.parametrize("randomness", ["fused", "cim"])
@pytest.mark.parametrize("execution", ["scan", "pallas"])
def test_sample_tokens_equals_reference(logits, randomness, execution):
    assert mh_kernel.lookup_by_window((B, V))
    key = jax.random.PRNGKey(5)
    got, result = _engine(randomness, execution).sample_tokens(
        key, logits, n_steps=K)
    ref = reference(key, logits, randomness)
    np.testing.assert_array_equal(np.asarray(got), ref["tokens"])
    np.testing.assert_array_equal(
        np.asarray(result.final_words)[:, 0], ref["final_words"])
    np.testing.assert_array_equal(
        np.asarray(result.accept_count)[:, 0], ref["accept_count"])
    # the check has teeth: some rows move, some proposals fall past V
    assert int(np.sum(ref["accept_count"])) > 0
    assert ref["proposed_out"] > 0


@pytest.mark.parametrize("execution", ["scan", "pallas"])
def test_words_past_the_vocabulary_never_accepted(execution):
    """Every proposal past V would be accepted if it were read as a
    finite log-prob: the table is flat, so the ratio of any finite
    proposal is 1."""
    flat = jnp.zeros((B, V), jnp.float32)
    _, result = _engine("fused", execution, collect="all").sample_tokens(
        jax.random.PRNGKey(2), flat, n_steps=K)
    samples = np.asarray(result.samples)
    assert samples.max() < V
    # every in-support proposal is accepted, so the chains do move
    assert np.asarray(result.accept_count).min() > 0


def test_window_lookup_matches_the_one_hot_lookup():
    """The two lookups of the MH kernels agree bit for bit where both
    apply (a single table; pallas interpret, operand kernel)."""
    table = jax.random.normal(jax.random.PRNGKey(3), (1, 300)) * 4.0
    init = jax.random.randint(
        jax.random.PRNGKey(4), (1, 16), 0, 300).astype(jnp.uint32)
    flips = jax.random.randint(
        jax.random.PRNGKey(6), (16, 1, 16), 0, 512).astype(jnp.uint32)
    u = jax.random.uniform(jax.random.PRNGKey(7), (16, 1, 16))

    def run(one_hot_max):
        """(outputs, DMAs in the program) with the path chosen by
        ``one_hot_max``; the path is fixed at trace time, so the
        kernel's traces are dropped around the call."""
        def call():
            return mh_kernel.mh_chain_pallas(
                table, init, flips, u, nbits=9, block_c=16)

        old = mh_kernel.ONE_HOT_MAX_V
        mh_kernel.ONE_HOT_MAX_V = one_hot_max
        mh_kernel.mh_chain_pallas.clear_cache()
        try:
            return call(), str(jax.make_jaxpr(call)()).count("dma_start")
        finally:
            mh_kernel.ONE_HOT_MAX_V = old
            mh_kernel.mh_chain_pallas.clear_cache()

    (one_hot, dmas_one_hot), (window, dmas_window) = run(300), run(0)
    assert dmas_one_hot == 0 and dmas_window > 0
    for a, b in zip(one_hot, window):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- the decode entry -----------------------------------------------------------


def test_decode_steps_compile_once_with_logits_traced(logits):
    """A decode step is one compiled program; new logits never
    recompile it."""
    engine = _engine("fused", "scan")
    before = engine_mod._sample_tokens_compiled._cache_size()
    for seed in range(3):
        table = logits + jnp.float32(seed)
        jax.block_until_ready(engine.sample_tokens(
            jax.random.PRNGKey(seed), table, n_steps=8))
    assert engine_mod._sample_tokens_compiled._cache_size() - before == 1


def test_sample_span_and_counters_with_stream_parity(logits):
    engine = _engine("fused", "pallas")
    key = jax.random.PRNGKey(9)
    off, r_off = engine.sample_tokens(key, logits, n_steps=16)
    rows = telemetry.counter("tokens_rows_total")
    steps = telemetry.counter("tokens_steps_total")
    rows0, steps0 = rows.value(), steps.value()
    telemetry.enable()
    try:
        on, r_on = engine.sample_tokens(key, logits, n_steps=16)
        spans = [e for e in telemetry.TRACER.events()
                 if e.kind == "span" and e.name == "tokens.sample"]
    finally:
        telemetry.disable()
    np.testing.assert_array_equal(np.asarray(off), np.asarray(on))
    np.testing.assert_array_equal(
        np.asarray(r_off.accept_count), np.asarray(r_on.accept_count))
    assert len(spans) == 1
    assert spans[0].meta["rows"] == B and spans[0].meta["n_steps"] == 16
    assert rows.value() - rows0 == B
    assert steps.value() - steps0 == B * 16


# --- the workload -----------------------------------------------------------------


def test_tokens_workload_runs_its_plan():
    wl = workloads.build("tokens", jax.random.PRNGKey(1), smoke=True,
                         randomness="fused", backend="scan", n_steps=32)
    assert wl.target.table.shape == (8, V)
    np.testing.assert_array_equal(
        np.asarray(wl.init_words)[:, 0],
        np.asarray(jnp.argmax(wl.target.table, axis=1)))
    result = wl.run(jax.random.PRNGKey(2))
    assert result.samples.shape == (32, 8, 1)
    assert np.asarray(result.samples).max() < V
    # the workload's table is the build's table 0, as sample_tokens sees it
    np.testing.assert_array_equal(
        np.asarray(wl.target.table),
        np.asarray(tokens.table_logits(jax.random.PRNGKey(1), 0, 8, V)))


def test_sample_cli_smoke_on_tokens():
    row = sample_cli.main([
        "--workload", "tokens", "--smoke", "--randomness", "fused",
        "--backend", "pallas", "--steps", "32",
    ])
    assert row["workload"] == "tokens"
    assert row["vocab"] == V and row["n_sites"] == 8
    assert 0.0 <= row["acceptance_rate"] <= 1.0
