"""Pallas TPU kernel: fused Metropolis-Hastings chain (paper §4, §5.2).

The entire K-step MH loop runs inside one kernel invocation with the chain
state resident in VREG/VMEM — the TPU analogue of the paper's
"the entire MCMC processing happens locally inside the macro":

  * the log-prob table block (the "stored distribution") sits in VMEM,
  * propose = XOR with a biased flip word        (block-wise pseudo-read),
  * accept test vs a debiased uniform            (accurate [0,1] RNG),
  * state update = select                        (in-memory copy),
  * only the kept sample stream is written back  (R/W circuits touched once
    per step instead of five times — same saving the paper measures).

Two kernels share that loop: ``mh_chain_pallas`` takes the random inputs
(flip words, uniforms) as (K, B, C) *operands* (host/cim randomness), and
``mh_chain_pallas_fused`` draws them in-kernel from the portable counter
cipher (kernels/rng), restoring the paper's zero-traffic randomness on
every substrate.  Both run compiled by Mosaic on TPU and in interpret mode
elsewhere, bit-identical to the scan executor on the CPU.

Grid: (B, C // BLOCK_C) — B independent targets (e.g. batch rows of logits),
C chains per target ("compartments").  BLOCK_C rides the 128-wide lane axis.
Every kernel value stays 2-D ``(1, BLOCK_C)`` (1-D vectors abort Mosaic's
layout pass), and the table lookup is a one-hot compare-and-max because
Mosaic has no lane gather (DESIGN.md §3).  The ``(1, BLOCK_C)`` row
blocks meet the (8, 128) tiling rule only for B = 1, so B > 1 token
tables do not compile for the chip yet.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import rng


def _lookup(table_col, words):
    """``table[words]``, and -inf for words outside [0, V).

    Mosaic lowers no lane gather over a (1, V) row, so the lookup is a
    one-hot compare over a (V, BC) tile followed by a max over V: every
    non-matching entry is -inf, so the max returns the matched value
    bit for bit (-0.0 and -inf included), and a word with no match
    (out of support) gets -inf — the ``TableTarget.log_prob`` semantics.
    Costs V x BC compares per step; fine for the V=256 grid tables, and
    vocabulary-width tables need V tiling instead."""
    vocab = table_col.shape[0]
    v = jax.lax.broadcasted_iota(jnp.int32, (vocab, words.shape[1]), 0)
    hit = v == words.astype(jnp.int32)
    return jnp.max(
        jnp.where(hit, table_col, -jnp.inf), axis=0, keepdims=True
    )


def _mh_kernel(
    table_ref,    # (1, V, 1) float32
    init_ref,     # (1, BC) uint32
    flips_ref,    # (K, 1, BC) uint32
    u_ref,        # (K, 1, BC) float32
    samples_ref,  # (K, 1, BC) uint32  out
    accept_ref,   # (1, BC) int32      out
    *,
    nbits: int,
    n_steps: int,
):
    table = table_ref[0]
    mask = jnp.uint32((1 << nbits) - 1)
    state0 = init_ref[...]
    logp0 = _lookup(table, state0)

    def body(k, carry):
        state, logp, acc = carry
        cand = jnp.bitwise_xor(state, flips_ref[k] & mask)
        logp_cand = _lookup(table, cand)
        delta = (logp_cand - logp).astype(jnp.float32)
        accept = jnp.logical_and(
            u_ref[k] < jnp.exp(jnp.minimum(delta, 0.0)),
            jnp.isfinite(logp_cand),
        )
        state = jnp.where(accept, cand, state)       # in-memory copy
        logp = jnp.where(accept, logp_cand, logp)
        samples_ref[k] = state
        return state, logp, acc + accept.astype(jnp.int32)

    _, _, acc = jax.lax.fori_loop(
        0, n_steps, body, (state0, logp0, jnp.zeros_like(state0, jnp.int32))
    )
    accept_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("nbits", "block_c", "interpret")
)
def mh_chain_pallas(
    table: jnp.ndarray,   # (B, V) float32
    init: jnp.ndarray,    # (B, C) uint32
    flips: jnp.ndarray,   # (K, B, C) uint32
    u: jnp.ndarray,       # (K, B, C) float32
    nbits: int,
    block_c: int = 256,
    interpret: bool = True,
):
    """Fused K-step MH over (B targets x C chains). C % block_c == 0."""
    b, vocab = table.shape
    k_steps, b2, c = flips.shape
    if (b2, c) != (b, init.shape[1]) or u.shape != flips.shape:
        raise ValueError(
            f"shape mismatch: table={table.shape} init={init.shape} "
            f"flips={flips.shape} u={u.shape}"
        )
    block_c = min(block_c, c)
    if c % block_c != 0:
        raise ValueError(f"C={c} not divisible by block_c={block_c}")

    kernel = functools.partial(_mh_kernel, nbits=nbits, n_steps=k_steps)
    samples, accept = pl.pallas_call(
        kernel,
        grid=(b, c // block_c),
        in_specs=[
            pl.BlockSpec((1, vocab, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((k_steps, 1, block_c), lambda i, j: (0, i, j)),
            pl.BlockSpec((k_steps, 1, block_c), lambda i, j: (0, i, j)),
        ],
        out_specs=[
            pl.BlockSpec((k_steps, 1, block_c), lambda i, j: (0, i, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_steps, b, c), jnp.uint32),
            jax.ShapeDtypeStruct((b, c), jnp.int32),
        ],
        interpret=interpret,
    )(
        table.astype(jnp.float32).reshape(b, vocab, 1),
        init.astype(jnp.uint32),
        flips,
        u,
    )
    return samples, accept


def _mh_fused_kernel(
    table_ref,    # (1, V, 1) float32
    init_ref,     # (1, BC) uint32
    k0_ref,       # (1, BC) uint32 per-column chain-key word 0
    k1_ref,       # (1, BC) uint32 per-column chain-key word 1
    t0_ref,       # (1, BC) int32 per-column absolute-step base
    samples_ref,  # (K, 1, BC) uint32  out
    accept_ref,   # (1, BC) int32      out
    *,
    nbits: int,
    n_steps: int,
    cc: int,
    p_u32: int,
):
    """In-kernel-RNG MH chain (DESIGN.md §Randomness): instead of (K,)
    operand planes, the kernel carries two uint32 key words per column
    and derives the flip word + accept uniform for absolute step
    ``t0 + k`` at site ``row * cc + col % cc`` with the shared counter
    cipher (kernels/rng) — the same functions the scan-side
    ``FusedRandomness`` reference draws through, so parity is by
    construction.  The absolute-step base ``t0`` is a per-column
    *operand* (not a compile-time constant): columns at different
    stream offsets — the serving tier's packed slots, tempering
    segments — share one compiled program, and the counter arithmetic
    is identical either way, so the stream is unchanged by
    construction.  ``cc`` is the per-chain column count (chains fold
    chain-major into the compartment axis, DESIGN.md §Chains-axis)."""
    table = table_ref[0]
    mask = jnp.uint32((1 << nbits) - 1)
    state0 = init_ref[...]
    k0 = k0_ref[...]
    k1 = k1_ref[...]
    t0 = t0_ref[...].astype(jnp.uint32)

    block_c = state0.shape[1]
    i = pl.program_id(0)
    j = pl.program_id(1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1)
    col = j * block_c + lane
    site = (i * cc + col % cc).astype(jnp.uint32)
    logp0 = _lookup(table, state0)

    def body(k, carry):
        state, logp, acc = carry
        s0, s1 = rng.step_key(k0, k1, t0 + k.astype(jnp.uint32))
        flip = rng.flips_at(s0, s1, site, nbits, p_u32)
        u = rng.uniform_at(s0, s1, site)
        cand = jnp.bitwise_xor(state, flip & mask)
        logp_cand = _lookup(table, cand)
        delta = (logp_cand - logp).astype(jnp.float32)
        accept = jnp.logical_and(
            u < jnp.exp(jnp.minimum(delta, 0.0)),
            jnp.isfinite(logp_cand),
        )
        state = jnp.where(accept, cand, state)       # in-memory copy
        logp = jnp.where(accept, logp_cand, logp)
        samples_ref[k] = state
        return state, logp, acc + accept.astype(jnp.int32)

    _, _, acc = jax.lax.fori_loop(
        0, n_steps, body, (state0, logp0, jnp.zeros_like(state0, jnp.int32))
    )
    accept_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=(
        "nbits", "n_steps", "cc", "p_u32", "block_c", "interpret"
    ),
)
def mh_chain_pallas_fused(
    table: jnp.ndarray,   # (B, V) float32
    init: jnp.ndarray,    # (B, C) uint32
    k0c: jnp.ndarray,     # (C,) uint32 per-column chain-key word 0
    k1c: jnp.ndarray,     # (C,) uint32 per-column chain-key word 1
    t0c: jnp.ndarray,     # (C,) int32 per-column absolute-step base
    *,
    nbits: int,
    n_steps: int,
    cc: int,
    p_u32: int,
    block_c: int = 256,
    interpret: bool = True,
):
    """Fused K-step MH with in-kernel RNG: zero per-step randomness
    operands — only the per-column key words + step base (12
    bytes/column/chunk) cross the kernel boundary.  ``t0c`` is the
    absolute step of the first chunk row, per column, as a *runtime
    operand* so chunks/slots at different stream offsets reuse one
    compiled program; ``cc`` the per-chain column count.
    C % block_c == 0."""
    b, vocab = table.shape
    c = init.shape[1]
    if k0c.shape != (c,) or k1c.shape != (c,) or t0c.shape != (c,):
        raise ValueError(
            f"per-column key/step words must be ({c},), got "
            f"{k0c.shape}/{k1c.shape}/{t0c.shape}"
        )
    block_c = min(block_c, c)
    if c % block_c != 0:
        raise ValueError(f"C={c} not divisible by block_c={block_c}")

    kernel = functools.partial(
        _mh_fused_kernel,
        nbits=nbits, n_steps=n_steps, cc=cc, p_u32=p_u32,
    )
    samples, accept = pl.pallas_call(
        kernel,
        grid=(b, c // block_c),
        in_specs=[
            pl.BlockSpec((1, vocab, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((n_steps, 1, block_c), lambda i, j: (0, i, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_steps, b, c), jnp.uint32),
            jax.ShapeDtypeStruct((b, c), jnp.int32),
        ],
        interpret=interpret,
    )(
        table.astype(jnp.float32).reshape(b, vocab, 1),
        init.astype(jnp.uint32),
        k0c.reshape(1, c),
        k1c.reshape(1, c),
        t0c.astype(jnp.int32).reshape(1, c),
    )
    return samples, accept

