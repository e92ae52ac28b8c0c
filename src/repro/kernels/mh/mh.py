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

Two lookups, chosen from the table's width (DESIGN.md §3):

  * grid tables (B = 1, V up to ``ONE_HOT_MAX_V``): grid (1, C //
    BLOCK_C), C chains ("compartments") with BLOCK_C on the 128-wide
    lane axis; the (1, V, 1) table block sits in VMEM and the lookup is
    a one-hot compare-and-max, as Mosaic has no lane gather.  Its
    (1, BLOCK_C) row blocks meet the (8, 128) tiling rule for B = 1
    only.
  * every other table (vocabulary widths, B > 1 rows): the (B, C)
    chains flatten to lanes — a
    decode step's one chain a row puts the rows on the lanes — and the
    table stays in HBM; each step fetches by DMA the 128-entry window
    that holds each lane's word and selects the entry with a 128-lane
    one-hot (``_window_lookup``), so a step costs the same at any V.
    Any B and C compile.

Every kernel value stays 2-D (1-D vectors abort Mosaic's layout pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rng

# Single tables (B = 1) up to this width take the one-hot lookup
# (``_lookup``); wider ones, and every B > 1, the window lookup
# (``_window_lookup``), whose cost per step does not grow with V.  The
# chip measured the one-hot path faster at V = 256 only; where the two
# cross between 256 and 2,048 is not measured.
ONE_HOT_MAX_V = 2048
WINDOW = 128          # entries of one table window: one lane row
LANE_BLOCK = 1024     # lanes (chains) one window-path grid step holds
VOCAB_KERNEL = "mh_vocab_window"


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _lookup(table_col, words):
    """``table[words]``, and -inf for words outside [0, V).

    Mosaic lowers no lane gather over a (1, V) row, so the lookup is a
    one-hot compare over a (V, BC) tile followed by a max over V: every
    non-matching entry is -inf, so the max returns the matched value
    bit for bit (-0.0 and -inf included), and a word with no match
    (out of support) gets -inf — the ``TableTarget.log_prob`` semantics.
    Costs V x BC compares per step: the path of the grid tables (V up
    to ``ONE_HOT_MAX_V``); wider tables take ``_window_lookup``."""
    vocab = table_col.shape[0]
    v = jax.lax.broadcasted_iota(jnp.int32, (vocab, words.shape[1]), 0)
    hit = v == words.astype(jnp.int32)
    return jnp.max(
        jnp.where(hit, table_col, -jnp.inf), axis=0, keepdims=True
    )


def _window_lookup(table_ref, scratch, *, vocab: int, rows: int, chains: int):
    """The lookup of vocabulary-width tables: ``lookup(words)`` gives
    ``table[row, words]``, and -inf for words outside [0, V), per lane.

    The table stays in HBM, viewed as ``(B / 8, W, 8, 128)``: window w
    of row b is ``[b // 8, w, b % 8]``, one 128-entry row of a tile, and
    the view is the (B, V) array's own tiled layout, so it costs no copy.
    Lane n of the block is chain ``n % chains`` of table row ``n //
    chains``.  Each call copies the lanes' window indices to SMEM (the
    scalar unit addresses DMAs; Mosaic moves no vector value to a
    scalar any other way), fetches the window that holds each lane's
    word by one 512-byte DMA, and selects the entry with a 128-lane
    one-hot compare and max — bit for bit the table's value, as in
    ``_lookup``.  A step costs one DMA per lane whatever V is.  Words
    past the table (V is rarely a multiple of 128 or a power of two) are
    clamped for the fetch and masked to -inf."""
    idx_vmem, idx_smem, buf, sem = scratch
    n = buf.shape[0]
    base = pl.program_id(0) * n

    def window(r):
        row = base + r if chains == 1 else (base + r) // chains
        row = jnp.minimum(row, rows - 1)
        return pltpu.make_async_copy(
            table_ref.at[row // 8, idx_smem[0, r], pl.ds(row % 8, 1)],
            buf.at[pl.ds(r, 1)],
            sem.at[1],
        )

    def start(r, carry):
        window(r).start()
        return carry

    def wait(r, carry):
        window(r).wait()
        return carry

    def lookup(words):
        inside = words < jnp.uint32(vocab)
        idx_vmem[...] = (                                  # w // WINDOW
            jnp.where(inside, words, 0).astype(jnp.int32) >> 7
        )
        to_smem = pltpu.make_async_copy(idx_vmem, idx_smem, sem.at[0])
        to_smem.start()
        to_smem.wait()
        jax.lax.fori_loop(0, n, start, 0)
        jax.lax.fori_loop(0, n, wait, 0)
        win = buf[...].T                                   # (128, n)
        lo = (words & jnp.uint32(WINDOW - 1)).astype(jnp.int32)
        hit = jax.lax.broadcasted_iota(jnp.int32, win.shape, 0) == lo
        val = jnp.max(jnp.where(hit, win, -jnp.inf), axis=0, keepdims=True)
        return jnp.where(inside, val, -jnp.inf)

    return lookup


def _window_scratch(n: int) -> list:
    """VMEM and SMEM scratch of ``_window_lookup`` for ``n`` lanes."""
    return [
        pltpu.VMEM((1, n), jnp.int32),
        pltpu.SMEM((1, n), jnp.int32),
        pltpu.VMEM((n, WINDOW), jnp.float32),
        pltpu.SemaphoreType.DMA((2,)),
    ]


def _mh_chain(lookup, draw, state0, samples_ref, accept_ref, *, nbits: int,
              n_steps: int):
    """THE kernel-side MH loop of every MH kernel: ``draw(k)`` gives step
    k's (flip word, uniform), ``lookup`` the table's log-prob of words.
    XOR-propose, accept on u < exp(min(dlogp, 0)) with a finite
    candidate, select (in-memory copy), write the step's state."""
    mask = jnp.uint32((1 << nbits) - 1)
    logp0 = lookup(state0)

    def body(k, carry):
        state, logp, acc = carry
        flip, u = draw(k)
        cand = jnp.bitwise_xor(state, flip & mask)
        logp_cand = lookup(cand)
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


def _mh_kernel(
    table_ref,    # (1, V, 1) float32, or (B/8, W, 8, 128) in HBM (window)
    init_ref,     # (1, BC) uint32
    flips_ref,    # (K, 1, BC) uint32
    u_ref,        # (K, 1, BC) float32
    samples_ref,  # (K, 1, BC) uint32  out
    accept_ref,   # (1, BC) int32      out
    *scratch,     # _window_scratch, on the window path only
    nbits: int,
    n_steps: int,
    window: dict | None = None,
):
    if window is None:
        table = table_ref[0]
        lookup = functools.partial(_lookup, table)
    else:
        lookup = _window_lookup(table_ref, scratch, **window)
    _mh_chain(
        lookup, lambda k: (flips_ref[k], u_ref[k]), init_ref[...],
        samples_ref, accept_ref, nbits=nbits, n_steps=n_steps,
    )


def lookup_by_window(shape: tuple) -> bool:
    """Whether a (B, V) table takes the window lookup: the one-hot
    compare costs V x BC per step and holds a (V, 128)-padded table
    block in VMEM, and its (1, BC) row blocks tile only for B = 1, so
    only single tables up to ``ONE_HOT_MAX_V`` keep it."""
    rows, vocab = shape
    return rows > 1 or vocab > ONE_HOT_MAX_V


def _window_call(kernel, table, chains: int, lanes: list, *, n_steps: int,
                 interpret: bool, **params):
    """Run an MH kernel on the window path.  The (B, C) chains flatten
    to lanes ``n = b * C + c``, padded to a whole lane block; the table
    stays in HBM as ``(B / 8, W, 8, 128)`` (``_window_lookup``), padded
    to whole tiles only where B is not a multiple of 8 or V of 128.
    ``lanes`` are the kernel's per-lane operands as (array, padding
    value) pairs, each array ``(1, N)`` or ``(K, 1, N)``.  Returns
    (samples (K, B, C), accept (B, C))."""
    rows, vocab = table.shape
    n = rows * chains
    block = min(_round_up(n, 128), LANE_BLOCK)
    n_pad = _round_up(n, block)
    width, height = _round_up(vocab, WINDOW), _round_up(rows, 8)
    table = jnp.pad(
        table.astype(jnp.float32), ((0, height - rows), (0, width - vocab))
    )
    tiles = table.reshape(height // 8, 8, width // WINDOW, WINDOW)

    def spec(shape):
        if len(shape) == 2:
            return pl.BlockSpec((1, block), lambda i: (0, i))
        return pl.BlockSpec((shape[0], 1, block), lambda i: (0, 0, i))

    padded = [
        jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n_pad - n)],
                constant_values=fill)
        for x, fill in lanes
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n_steps, 1, n_pad), jnp.uint32),
        jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
    ]
    samples, accept = pl.pallas_call(
        functools.partial(
            kernel, n_steps=n_steps,
            window=dict(vocab=vocab, rows=rows, chains=chains), **params,
        ),
        grid=(n_pad // block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
        + [spec(x.shape) for x in padded],
        out_specs=[spec(o.shape) for o in out_shape],
        out_shape=out_shape,
        scratch_shapes=_window_scratch(block),
        interpret=interpret,
        name=VOCAB_KERNEL,
    )(tiles.transpose(0, 2, 1, 3), *padded)
    return (samples[:, 0, :n].reshape(n_steps, rows, chains),
            accept[0, :n].reshape(rows, chains))


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
    """Fused K-step MH over (B targets x C chains).  A single table up
    to ``ONE_HOT_MAX_V`` wide runs a grid of (1, block_c) chain blocks
    (C % block_c == 0); every other the window path, any B and C
    (``lookup_by_window``)."""
    b, vocab = table.shape
    k_steps, b2, c = flips.shape
    if (b2, c) != (b, init.shape[1]) or u.shape != flips.shape:
        raise ValueError(
            f"shape mismatch: table={table.shape} init={init.shape} "
            f"flips={flips.shape} u={u.shape}"
        )
    if lookup_by_window(table.shape):
        n = b * c
        return _window_call(
            _mh_kernel, table, c,
            [(init.astype(jnp.uint32).reshape(1, n), 0),
             (flips.reshape(k_steps, 1, n), 0),
             (u.reshape(k_steps, 1, n), 1.0)],
            n_steps=k_steps, interpret=interpret, nbits=nbits,
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
    table_ref,    # (1, V, 1) float32, or (B/8, W, 8, 128) in HBM (window)
    init_ref,     # (1, BC) uint32
    k0_ref,       # (1, BC) uint32 per-column chain-key word 0
    k1_ref,       # (1, BC) uint32 per-column chain-key word 1
    t0_ref,       # (1, BC) int32 per-column absolute-step base
    *refs,        # [site (1, BC) uint32 (window)], samples (K, 1, BC)
                  # uint32 out, accept (1, BC) int32 out, [scratch]
    nbits: int,
    n_steps: int,
    cc: int,
    p_u32: int,
    window: dict | None = None,
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
    chain-major into the compartment axis, DESIGN.md §Chains-axis).  On
    the window path the lanes carry whole (row, chain) pairs, so the
    site arrives as a per-lane operand."""
    k0 = k0_ref[...]
    k1 = k1_ref[...]
    t0 = t0_ref[...].astype(jnp.uint32)
    if window is None:
        samples_ref, accept_ref = refs
        block_c = init_ref.shape[1]
        i = pl.program_id(0)
        j = pl.program_id(1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1)
        col = j * block_c + lane
        site = (i * cc + col % cc).astype(jnp.uint32)
        lookup = functools.partial(_lookup, table_ref[0])
    else:
        site_ref, samples_ref, accept_ref, *scratch = refs
        site = site_ref[...]
        lookup = _window_lookup(table_ref, scratch, **window)

    def draw(k):
        s0, s1 = rng.step_key(k0, k1, t0 + k.astype(jnp.uint32))
        return (rng.flips_at(s0, s1, site, nbits, p_u32),
                rng.uniform_at(s0, s1, site))

    _mh_chain(lookup, draw, init_ref[...], samples_ref, accept_ref,
              nbits=nbits, n_steps=n_steps)


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
    compiled program; ``cc`` the per-chain column count.  Grid or
    window path as ``mh_chain_pallas``."""
    b, vocab = table.shape
    c = init.shape[1]
    if k0c.shape != (c,) or k1c.shape != (c,) or t0c.shape != (c,):
        raise ValueError(
            f"per-column key/step words must be ({c},), got "
            f"{k0c.shape}/{k1c.shape}/{t0c.shape}"
        )
    if lookup_by_window(table.shape):
        n = b * c

        def lanes(x):
            return jnp.broadcast_to(x, (b, c)).reshape(1, n)

        col = jnp.arange(c, dtype=jnp.uint32)
        site = jnp.arange(b, dtype=jnp.uint32)[:, None] * cc + col % cc
        return _window_call(
            _mh_fused_kernel, table, c,
            [(lanes(init.astype(jnp.uint32)), 0), (lanes(k0c), 0),
             (lanes(k1c), 0), (lanes(t0c.astype(jnp.int32)), 0),
             (lanes(site), 0)],
            n_steps=n_steps, interpret=interpret, nbits=nbits, cc=cc,
            p_u32=p_u32,
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

