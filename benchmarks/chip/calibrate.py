"""Threefry-2x32 block rate of the chip, measured on a kernel of our own.

The sampling step's roofline has two legs: the Threefry blocks that the
fused stream contract requires, and the bytes a job must move.  HBM
bandwidth has a published peak (``peaks.json``); the cipher's block
rate has none, so the traced run measures it here: a Pallas kernel that
does nothing but Threefry-2x32-20 on full (8, 128)-tiled vregs, with
its outputs folded by XOR so that no HBM traffic bounds it.

The cipher is the benchmark's own (``stream.py``), not the program's,
so a later change to the program cannot move this yardstick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from stream import threefry2x32

# One grid step holds a (ROWS, 128) tile: 8 vregs for each of the two
# counter words and the accumulator, well inside the 64 vregs of a core.
ROWS = 64
LANES = 128
GRID = 256
ITERS = 512
BLOCKS = GRID * ROWS * LANES * ITERS    # cipher blocks per call
KERNEL_NAME = "threefry_calibration"


def _kernel(key_ref, out_ref, *, iters: int):
    k0 = key_ref[0]
    k1 = key_ref[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1)
    base = (pl.program_id(0) * ROWS + row) * LANES + lane
    x0 = base.astype(jnp.uint32)

    def body(i, acc):
        y0, y1 = threefry2x32(k0, k1, x0, jnp.full_like(x0, i).astype(jnp.uint32))
        return acc ^ y0 ^ y1

    out_ref[...] = jax.lax.fori_loop(0, iters, body, jnp.zeros_like(x0))


@functools.partial(jax.jit, static_argnames=("interpret", "grid", "iters"))
def threefry_calibration(key_words, *, interpret: bool = False,
                         grid: int = GRID, iters: int = ITERS):
    """``grid * ROWS * LANES * iters`` Threefry blocks; returns the
    XOR-folded outputs, (grid * ROWS, LANES) uint32."""
    return pl.pallas_call(
        functools.partial(_kernel, iters=iters),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid * ROWS, LANES), jnp.uint32),
        interpret=interpret,
        name=KERNEL_NAME,
    )(key_words)


def reference_fold(key_words, grid: int, iters: int):
    """The same XOR fold in plain ``jax.numpy`` (for the kernel's test)."""
    k0, k1 = key_words[0], key_words[1]
    x0 = jnp.arange(grid * ROWS * LANES, dtype=jnp.uint32).reshape(-1, LANES)
    acc = jnp.zeros_like(x0)
    for i in range(iters):
        y0, y1 = threefry2x32(k0, k1, x0, jnp.full_like(x0, i))
        acc = acc ^ y0 ^ y1
    return acc
