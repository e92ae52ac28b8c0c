"""Pallas TPU kernel: fused checkerboard Gibbs sweep over a 2-D MRF.

The K-half-sweep Gibbs loop runs inside one kernel invocation with the
whole lattice resident in VREG/VMEM — the Gibbs analogue of the fused MH
chain (kernels/mh/mh.py).  Per half-sweep:

  * conditional logit from the model's ``logit_fn`` (e.g. the Ising
    4-neighbour coupling, periodic boundary via rolls) — the *same*
    function the scan executor calls, traced into the kernel as a static
    closure, so scan/pallas share one conditional implementation,
  * conditional flip  = u < sigmoid(logit)  (accurate [0,1] RNG operand —
    the same uniform stream the MH accept test consumes),
  * only the active checkerboard colour is rewritten (the two-colour
    sweep keeps every update's neighbourhood fixed, so all sites of one
    colour flip in parallel exactly as the macro's compartments do).

``gibbs_chain_pallas`` takes the uniforms as (K, B, H, W) *operands*
(host/cim randomness); ``gibbs_chain_pallas_fused`` draws them in-kernel
from the counter cipher (kernels/rng), exactly like the fused MH kernel.

Grid: (B,) — B independent lattices, one (H, W) block each.  W rides the
128-wide lane axis; a periodic lattice cannot be zero-padded, so compiled
TPU execution wants W as a lane multiple while interpret mode (CPU) takes
any shape.  Per-lattice scalars ride whole (B,) arrays in SMEM.  A grid
step holds the lattice plus K sample planes (and K uniform planes with
operands) in VMEM: on v5e, 128x128 lattices compile at K = 32 in both
modes, 256x256 only fused at K = 16, 512x512 not at all (DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rng

# Per-lattice scalars (parity, key words, step base) travel as whole (B,)
# arrays in SMEM, read at ``program_id(0)``: a (1, 1) VMEM block of a
# (B, 1) array breaks the (8, 128) tiling rule of compiled TPU blocks.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _gibbs_kernel(
    init_ref,     # (1, H, W) uint32 {0,1} spins
    u_ref,        # (K, 1, H, W) float32
    parity_ref,   # (B,) int32 SMEM: every lattice's starting parity
    *rest,        # n_consts broadcast model refs, then the two outputs:
                  #   samples (K, 1, H, W) uint32, flips (1, H, W) int32
    logit_fn,
    n_steps: int,
    n_consts: int,
):
    const_refs, (samples_ref, flips_ref) = rest[:n_consts], rest[n_consts:]
    consts = tuple(ref[...] for ref in const_refs)
    state0 = init_ref[0]
    parity0 = parity_ref[pl.program_id(0)]
    h, w = state0.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)
    checker = (row + col) % 2

    def body(k, carry):
        state, nflips = carry
        parity = (parity0 + k) % 2
        new = (
            u_ref[k, 0] < jax.nn.sigmoid(logit_fn(state, *consts))
        ).astype(jnp.uint32)
        nxt = jnp.where(checker == parity, new, state)
        samples_ref[k, 0] = nxt
        return nxt, nflips + (nxt != state).astype(jnp.int32)

    _, nflips = jax.lax.fori_loop(
        0, n_steps, body, (state0, jnp.zeros_like(state0, jnp.int32))
    )
    flips_ref[0] = nflips


@functools.partial(
    jax.jit, static_argnames=("logit_fn", "interpret")
)
def gibbs_chain_pallas(
    init: jnp.ndarray,  # (B, H, W) uint32 {0,1} spins
    u: jnp.ndarray,     # (K, B, H, W) float32
    logit_fn,           # (H, W) state [, *consts] -> (H, W) logit of s=1
    parity0=0,          # int or (B,) int32 starting checkerboard parity
    interpret: bool = True,
    consts: tuple = (),
):
    """Fused K-half-sweep checkerboard Gibbs over B independent lattices.

    ``logit_fn`` must be hashable (it rides a jit static argument) — a
    bound method of a frozen model dataclass qualifies.  Models whose
    conditional closes over *array* parameters (e.g. spin-glass bond
    couplings) cannot capture them in the kernel trace; they arrive as
    ``consts`` operands instead, broadcast to every grid step, and
    ``logit_fn(state, *consts)`` threads them back into the one shared
    conditional implementation (DESIGN.md §Tempering).

    ``parity0`` is a runtime operand (scalar or per-lattice ``(B,)``),
    so lattices at different absolute steps — packed serving slots —
    share one compiled program.
    """
    b, h, w = init.shape
    k_steps = u.shape[0]
    if u.shape != (k_steps, b, h, w):
        raise ValueError(
            f"shape mismatch: init={init.shape} u={u.shape}"
        )
    parity0b = jnp.broadcast_to(jnp.asarray(parity0, jnp.int32), (b,))
    kernel = functools.partial(
        _gibbs_kernel,
        logit_fn=logit_fn,
        n_steps=k_steps,
        n_consts=len(consts),
    )
    const_specs = [
        pl.BlockSpec(c.shape, functools.partial(lambda nd, i: (0,) * nd, c.ndim))
        for c in consts
    ]
    samples, flips = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((k_steps, 1, h, w), lambda i: (0, i, 0, 0)),
            _SMEM,
            *const_specs,
        ],
        out_specs=[
            pl.BlockSpec((k_steps, 1, h, w), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_steps, b, h, w), jnp.uint32),
            jax.ShapeDtypeStruct((b, h, w), jnp.int32),
        ],
        interpret=interpret,
    )(init.astype(jnp.uint32), u, parity0b, *consts)
    return samples, flips


def _gibbs_fused_kernel(
    init_ref,     # (1, H, W) uint32 {0,1} spins
    k0_ref,       # (B,) uint32 SMEM: per-lattice chain-key word 0
    k1_ref,       # (B,) uint32 SMEM: per-lattice chain-key word 1
    t0_ref,       # (B,) int32 SMEM: per-lattice absolute-step base
    *rest,        # n_consts broadcast model refs, then the two outputs:
                  #   samples (K, 1, H, W) uint32, flips (1, H, W) int32
    logit_fn,
    n_steps: int,
    lat_b: int,
    n_consts: int,
):
    """In-kernel-RNG checkerboard Gibbs (DESIGN.md §Randomness): no
    uniform operand planes — the kernel carries this lattice's two
    chain-key words and derives the site uniforms for absolute step
    ``t0 + k`` with the shared counter cipher (kernels/rng), exactly the
    draws the scan-side ``FusedRandomness`` reference makes.  ``lat_b``
    is the per-chain lattice-batch size (chains fold into the batch
    grid axis, DESIGN.md §Chains-axis), so lattice ``i`` covers sites
    ``(i % lat_b) * H * W + h * W + w``.  The absolute-step base ``t0``
    is a per-lattice *operand* — lattices at different stream offsets
    (packed serving slots, successive chunks) share one compiled
    program, and both the counter and the checkerboard parity
    (absolute step mod 2) derive from it in-kernel, so the stream is
    unchanged by construction."""
    const_refs, (samples_ref, flips_ref) = rest[:n_consts], rest[n_consts:]
    consts = tuple(ref[...] for ref in const_refs)
    state0 = init_ref[0]
    i = pl.program_id(0)
    k0 = k0_ref[i]
    k1 = k1_ref[i]
    t0 = t0_ref[i].astype(jnp.uint32)
    h, w = state0.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)
    checker = (row + col) % 2
    site = ((i % lat_b) * h * w + row * w + col).astype(jnp.uint32)

    def body(k, carry):
        state, nflips = carry
        t = t0 + k.astype(jnp.uint32)
        parity = (t % 2).astype(jnp.int32)
        s0, s1 = rng.step_key(k0, k1, t)
        u = rng.uniform_at(s0, s1, site)
        new = (u < jax.nn.sigmoid(logit_fn(state, *consts))).astype(
            jnp.uint32
        )
        nxt = jnp.where(checker == parity, new, state)
        samples_ref[k, 0] = nxt
        return nxt, nflips + (nxt != state).astype(jnp.int32)

    _, nflips = jax.lax.fori_loop(
        0, n_steps, body, (state0, jnp.zeros_like(state0, jnp.int32))
    )
    flips_ref[0] = nflips


@functools.partial(
    jax.jit,
    static_argnames=("logit_fn", "n_steps", "lat_b", "interpret"),
)
def gibbs_chain_pallas_fused(
    init: jnp.ndarray,  # (B, H, W) uint32 {0,1} spins
    k0b: jnp.ndarray,   # (B,) uint32 per-lattice chain-key word 0
    k1b: jnp.ndarray,   # (B,) uint32 per-lattice chain-key word 1
    t0b: jnp.ndarray,   # (B,) int32 per-lattice absolute-step base
    logit_fn,           # (H, W) state [, *consts] -> (H, W) logit of s=1
    *,
    n_steps: int,
    lat_b: int,
    interpret: bool = True,
    consts: tuple = (),
):
    """Fused K-half-sweep Gibbs with in-kernel RNG: zero per-step
    randomness operands — only the per-lattice key words + step base
    (12 bytes/lattice/chunk) cross the kernel boundary.  ``t0b`` is the
    absolute step of the first half-sweep per lattice (parity =
    t0 % 2), a *runtime operand* so lattices at different stream
    offsets share one compiled program.  Same ``logit_fn``/``consts``
    contract as ``gibbs_chain_pallas``."""
    b, h, w = init.shape
    if k0b.shape != (b,) or k1b.shape != (b,) or t0b.shape != (b,):
        raise ValueError(
            f"per-lattice key/step words must be ({b},), got "
            f"{k0b.shape}/{k1b.shape}/{t0b.shape}"
        )
    kernel = functools.partial(
        _gibbs_fused_kernel,
        logit_fn=logit_fn,
        n_steps=n_steps,
        lat_b=lat_b,
        n_consts=len(consts),
    )
    const_specs = [
        pl.BlockSpec(c.shape, functools.partial(lambda nd, i: (0,) * nd, c.ndim))
        for c in consts
    ]
    samples, flips = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
            _SMEM,
            _SMEM,
            _SMEM,
            *const_specs,
        ],
        out_specs=[
            pl.BlockSpec((n_steps, 1, h, w), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_steps, b, h, w), jnp.uint32),
            jax.ShapeDtypeStruct((b, h, w), jnp.int32),
        ],
        interpret=interpret,
    )(
        init.astype(jnp.uint32),
        k0b,
        k1b,
        t0b.astype(jnp.int32),
        *consts,
    )
    return samples, flips
