"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) accepts programs the chip's
Mosaic compiler refuses: unaligned block shapes, lane gathers, uint32
-> float32 casts, too much VMEM.  These tests compile each kernel with
``interpret=False`` for a *described* v5e chip — nothing runs, and no
TPU is needed — at the widths the workloads deploy: the gmm table
(V=256) with 4096 compartment chains, a decode step's (256, 129,280)
logits table with one chain a row, and 8 lattices of 128x128 with one
32-step chunk.

The topology is described inside a module fixture (never at import):
only one process may load the TPU library, and pytest-xdist workers
all import this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import rng
from repro.kernels.gibbs.gibbs import (
    gibbs_chain_pallas,
    gibbs_chain_pallas_fused,
)
from repro.kernels.mh.mh import (
    VOCAB_KERNEL,
    mh_chain_pallas,
    mh_chain_pallas_fused,
)
from repro.workloads.ising import IsingModel
from repro.workloads.spin_glass import SpinGlass

V, C, BLOCK_C = 256, 4096, 256      # gmm nbits=8 table, compartment chains
ROWS, VOCAB = 256, 129_280          # decode batch x DeepSeek-V3 vocabulary
TOKEN_K = 64                        # MH steps per token
LATTICES, SIDE = 8, 128             # Gibbs lattice batch, 128x128 sites
K = 32                              # steps per chunk (workload default)


@pytest.fixture(scope="module")
def chip():
    """A sharding on one described v5e chip, with the persistent
    compilation cache off: a compile for a described chip is written to
    the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_mh_operand_compiles(chip):
    def run(table, init, flips, u):
        return mh_chain_pallas(
            table, init, flips, u, nbits=8, block_c=BLOCK_C, interpret=False
        )

    _assert_kernel(_compile(
        run, chip,
        ((1, V), jnp.float32), ((1, C), jnp.uint32),
        ((K, 1, C), jnp.uint32), ((K, 1, C), jnp.float32),
    ))


def test_mh_fused_compiles(chip):
    def run(table, init, k0c, k1c, t0c):
        return mh_chain_pallas_fused(
            table, init, k0c, k1c, t0c, nbits=8, n_steps=K, cc=C,
            p_u32=rng.threshold_u32(0.45), block_c=BLOCK_C, interpret=False,
        )

    _assert_kernel(_compile(
        run, chip,
        ((1, V), jnp.float32), ((1, C), jnp.uint32),
        ((C,), jnp.uint32), ((C,), jnp.uint32), ((C,), jnp.int32),
    ))


# One chain per row of a decode step's (256, 129,280) logits, and of a
# small (8, 128) table: both take the window lookup (B > 1 rows), whose
# table stays in HBM.
TOKEN_TABLES = [(ROWS, VOCAB), (8, 128)]


@pytest.mark.parametrize("rows,vocab", TOKEN_TABLES)
def test_mh_vocab_operand_compiles(chip, rows, vocab):
    nbits = (vocab - 1).bit_length()

    def run(table, init, flips, u):
        return mh_chain_pallas(
            table, init, flips, u, nbits=nbits, interpret=False
        )

    compiled = _compile(
        run, chip,
        ((rows, vocab), jnp.float32), ((rows, 1), jnp.uint32),
        ((TOKEN_K, rows, 1), jnp.uint32), ((TOKEN_K, rows, 1), jnp.float32),
    )
    _assert_kernel(compiled)
    assert VOCAB_KERNEL in compiled.as_text()


@pytest.mark.parametrize("rows,vocab", TOKEN_TABLES)
def test_mh_vocab_fused_compiles(chip, rows, vocab):
    nbits = (vocab - 1).bit_length()

    def run(table, init, k0c, k1c, t0c):
        return mh_chain_pallas_fused(
            table, init, k0c, k1c, t0c, nbits=nbits, n_steps=TOKEN_K, cc=1,
            p_u32=rng.threshold_u32(0.45), interpret=False,
        )

    compiled = _compile(
        run, chip,
        ((rows, vocab), jnp.float32), ((rows, 1), jnp.uint32),
        ((1,), jnp.uint32), ((1,), jnp.uint32), ((1,), jnp.int32),
    )
    _assert_kernel(compiled)
    assert VOCAB_KERNEL in compiled.as_text()


def _lattice_model(name):
    """(logit_fn, const shapes) of a lattice workload at SIDE x SIDE."""
    if name == "ising":
        return IsingModel(SIDE, SIDE).conditional_logit, ()
    glass = SpinGlass.bimodal(jax.random.PRNGKey(0), SIDE, SIDE)
    return glass.fused_logit, tuple(
        (c.shape, c.dtype) for c in glass.fused_consts
    )


@pytest.mark.parametrize("model", ["ising", "spin_glass"])
def test_gibbs_operand_compiles(chip, model):
    logit_fn, const_shapes = _lattice_model(model)

    def run(init, u, parity0, *consts):
        return gibbs_chain_pallas(
            init, u, logit_fn, parity0=parity0, interpret=False,
            consts=consts,
        )

    _assert_kernel(_compile(
        run, chip,
        ((LATTICES, SIDE, SIDE), jnp.uint32),
        ((K, LATTICES, SIDE, SIDE), jnp.float32),
        ((LATTICES,), jnp.int32),
        *const_shapes,
    ))


@pytest.mark.parametrize("model", ["ising", "spin_glass"])
def test_gibbs_fused_compiles(chip, model):
    logit_fn, const_shapes = _lattice_model(model)

    def run(init, k0b, k1b, t0b, *consts):
        return gibbs_chain_pallas_fused(
            init, k0b, k1b, t0b, logit_fn, n_steps=K, lat_b=LATTICES,
            interpret=False, consts=consts,
        )

    _assert_kernel(_compile(
        run, chip,
        ((LATTICES, SIDE, SIDE), jnp.uint32),
        ((LATTICES,), jnp.uint32), ((LATTICES,), jnp.uint32),
        ((LATTICES,), jnp.int32),
        *const_shapes,
    ))
