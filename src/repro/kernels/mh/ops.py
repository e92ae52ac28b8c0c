"""Public jit'd wrappers for the fused MH kernel.

``mh_sample`` is the raw kernel entry (randomness as operands).
``mh_sample_with_rng`` generates the paper-faithful randomness — biased flip
words from pseudo-read bit-planes, uniforms via the MSXOR kernel — and runs
the fused chain.  ``sample_tokens_fused`` is the serving-path entry: one
chain per batch row over that row's logits (softmax-free token sampling).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import rng
from repro.kernels.mh.mh import (
    lookup_by_window,
    mh_chain_pallas,
    mh_chain_pallas_fused,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _chain_block(table, c: int, block_c: int) -> tuple[int, int]:
    """(block, padded C) of the kernel's chain axis.  The window path
    flattens and pads (row, chain) lanes itself, so its chain axis
    stays as it is."""
    if lookup_by_window(table.shape):
        return block_c, c
    bc = min(block_c, _round_up(c, 128))
    return bc, _round_up(c, bc)


def mh_sample(table, init, flips, u, nbits: int, block_c: int = 256):
    """Pad the chain axis to a lane multiple and run the fused kernel.

    Emits every step of the chunk; the engine's shared chunk scheduler
    (``_drive_pallas_chunks``) slices what its collection mode keeps
    into a preallocated stream buffer (DESIGN.md §Collection)."""
    b, c = init.shape
    bc, c_pad = _chain_block(table, c, block_c)
    if c_pad != c:
        pad = c_pad - c
        init = jnp.pad(init, ((0, 0), (0, pad)))
        flips = jnp.pad(flips, ((0, 0), (0, 0), (0, pad)))
        u = jnp.pad(u, ((0, 0), (0, 0), (0, pad)), constant_values=1.0)
    samples, accept = mh_chain_pallas(
        table, init, flips, u, nbits=nbits, block_c=bc, interpret=not _on_tpu()
    )
    return samples[:, :, :c], accept[:, :c]


def mh_sample_fused(
    table, init, k0c, k1c, *, n_steps: int, t0, nbits: int,
    p_bfr: float, cc: int, block_c: int = 256,
):
    """In-kernel-RNG edition of ``mh_sample`` (randomness="fused"): the
    chunk's randomness never exists as an operand — ``k0c``/``k1c`` are
    the per-column chain-key words (8 bytes per column per chunk, vs
    8 bytes per site per *step* for shipped operands) and the kernel
    derives each step's flip word + uniform from the ``(t0 + k, site)``
    counter (DESIGN.md §Randomness).  ``t0`` is an int or per-column
    (C,) int32 array — a runtime operand, so columns at different
    absolute steps (packed serving slots, successive chunks) share one
    compiled program.  ``cc`` is the per-chain column count (the solo
    chain width; multi-chain callers fold chains chain-major).  Padding
    columns carry zero keys; their chains evolve under the zero-key
    stream and are sliced off like the operand path's u=1.0 padding."""
    b, c = init.shape
    t0c = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (c,))
    bc, c_pad = _chain_block(table, c, block_c)
    if c_pad != c:
        pad = c_pad - c
        init = jnp.pad(init, ((0, 0), (0, pad)))
        k0c = jnp.pad(k0c, (0, pad))
        k1c = jnp.pad(k1c, (0, pad))
        t0c = jnp.pad(t0c, (0, pad))
    samples, accept = mh_chain_pallas_fused(
        table, init, k0c, k1c, t0c, nbits=nbits, n_steps=n_steps, cc=cc,
        p_u32=rng.threshold_u32(p_bfr), block_c=bc, interpret=not _on_tpu(),
    )
    return samples[:, :, :c], accept[:, :c]


class MHRandomness(NamedTuple):
    flips: jnp.ndarray  # (K, B, C) uint32 biased flip words
    u: jnp.ndarray      # (K, B, C) float32 MSXOR-debiased uniforms


def generate_randomness(
    key,
    n_steps: int,
    batch: int,
    chains: int,
    p_bfr: float,
    rng_stages: int = 3,
) -> MHRandomness:
    """Paper-faithful randomness: pseudo-read bit-planes + MSXOR uniforms.

    Thin materialising wrapper over ``samplers.CIMRandomness`` — the one
    place the pseudo-read + MSXOR operand recipe (and its
    ``(k_flip, k_u)`` step-key split) lives, so kernel-level callers and
    the engine draw the *same* stream.  Materialises the full (K, B, C)
    operand block up front — fine for kernel tests/benchmarks, but long
    chains should stream chunks through the backend (DESIGN.md §2)."""
    from repro.samplers.randomness import (  # deferred: samplers imports us
        CIMRandomness,
    )

    backend = CIMRandomness(
        p_bfr=p_bfr, rng_p_bfr=p_bfr, rng_bit_width=32,
        rng_stages=rng_stages,
    )
    flips, u = backend.chunk(key, 0, n_steps, (batch, chains), nbits=32)
    return MHRandomness(flips=flips, u=u)


def mh_sample_with_rng(
    key,
    table,
    n_steps: int,
    chains: int = 1,
    p_bfr: float = 0.45,
    rng_stages: int = 3,
    init: jnp.ndarray | None = None,
    nbits: int | None = None,
):
    """End-to-end fused sampling from a (B, V) log-prob table."""
    b, vocab = table.shape
    if nbits is None:
        nbits = max(1, math.ceil(math.log2(vocab)))
    if init is None:
        init = jnp.broadcast_to(
            jnp.argmax(table, axis=-1).astype(jnp.uint32)[:, None], (b, chains)
        )
    rnd = generate_randomness(key, n_steps, b, chains, p_bfr, rng_stages)
    return mh_sample(table, init, rnd.flips, rnd.u, nbits=nbits)


def sample_tokens_fused(
    key,
    logits,
    n_steps: int = 64,
    temperature: float = 1.0,
    p_bfr: float = 0.45,
    prev_tokens: jnp.ndarray | None = None,
):
    """Serving-path token sampler: one fused MH chain per batch row.

    Thin wrapper over the unified engine with pallas execution forced —
    kept so kernel-level callers keep a one-call entry.  Returns
    (tokens (B,) int32, acceptance_rate scalar).
    """
    from repro import samplers  # deferred: samplers imports this module

    engine = samplers.MHEngine(
        samplers.EngineConfig(p_bfr=p_bfr, execution="pallas")
    )
    tokens, result = engine.sample_tokens(
        key,
        logits,
        n_steps=n_steps,
        temperature=temperature,
        init_tokens=prev_tokens,
    )
    return tokens, result.acceptance_rate
