"""Counter-based in-kernel RNG — the paper's RNG-inside-the-memory, fused.

Every other randomness backend in this repo materialises operand blocks
on host and ships them to the executor.  This module is the third way
(DESIGN.md §Randomness): a *counter-based* generator whose draw for
``(chain, absolute step t, site s)`` is a pure function of the chain
key and the ``(t, s)`` counter, implemented entirely in elementwise
uint32 arithmetic — add/xor/rotate/shift/compare — so the *same
functions* trace both into the Pallas kernel bodies and into the
scan-side reference backend (``samplers.FusedRandomness``).  Bit-parity
between executors is therefore by construction, not by mirroring.

The block cipher is Threefry-2x32 with 20 rounds (Salmon et al.,
"Parallel random numbers: as easy as 1, 2, 3" — the same cipher behind
``jax.random``'s default PRNG, reimplemented here because the kernel
body cannot call ``jax.random``).  Statistically it passes Crush-level
test batteries; its per-bit bias is 0 by construction, comfortably
inside the paper's <1e-5 deviation budget for the accurate-[0,1] RNG
(empirically pinned in tests/test_fused_rng.py).

Derivation contract (mirrors the engine's ``fold_in`` chain, DESIGN.md
§Chains-axis):

    chain fold   jax-side:  key_c = jax.random.fold_in(key, chain_id)
    key words    (k0, k1) = key_words(key_c)          # 2x uint32
    step fold    (s0, s1) = step_key(k0, k1, t)       # t = absolute step
    site draw    bits     = threefry2x32(s0, s1, site, salt)[0]

``site`` is the linear index into the *per-chain* state block (row-major
over the solo-run shape), and ``salt`` separates the operand streams —
``U_SALT`` for the accept/flip uniform, ``FLIP_SALT + i`` for proposal
bit-plane i — so consuming one operand can never perturb another (the
``need_flips`` invariance, DESIGN.md §Collection).  Everything after the
chain fold runs wherever the consumer lives: on host for the scan
reference, inside the kernel for the fused executors, with only the two
carried key words crossing the operand boundary.

Where available, TPU hardware PRNG primitives (``pltpu.prng_seed`` /
``prng_random_bits``) could replace the cipher's draw stage, but they
have no interpret-mode lowering and draw from a different stream, which
would break the scan<->pallas bit-parity contract; this repo keeps the
portable cipher everywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Threefry-2x32 rotation schedule: rounds 4i..4i+3 use ROTATIONS[i % 2].
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
# Key-schedule parity constant (the 2x32 slice of the Threefish C240).
_PARITY = 0x1BD11BDA

# Operand-stream salts (second counter word).  FLIP planes occupy
# [FLIP_SALT, FLIP_SALT + 32); U_SALT lives far outside that window.
U_SALT = 0x554E4946  # "UNIF"
FLIP_SALT = 0x464C4950  # "FLIP"


def _u32(x) -> jnp.ndarray:
    if isinstance(x, int):  # python ints coerce via int32 and overflow
        return jnp.uint32(x & 0xFFFFFFFF)
    return jnp.asarray(x).astype(jnp.uint32)


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """One Threefry-2x32-20 block: counter (x0, x1) under key (k0, k1).

    All inputs broadcast together; everything is elementwise uint32
    add/xor/rotate, so this traces identically on host, under scan, and
    inside a Pallas kernel body (interpret or compiled).
    """
    k0, k1, x0, x1 = _u32(k0), _u32(k1), _u32(x0), _u32(x1)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def key_words(key) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The two uint32 key words of a jax PRNG key (typed or raw)."""
    if hasattr(key, "dtype") and jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    flat = _u32(key).reshape(-1)
    return flat[0], flat[1]


def step_key(k0, k1, t) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fold absolute step ``t`` into the chain key (one cipher block)."""
    return threefry2x32(k0, k1, _u32(t), jnp.uint32(0))


def raw_draw(s0, s1, site, salt: int) -> jnp.ndarray:
    """One uint32 of stream ``salt`` at each ``site`` under step key."""
    return threefry2x32(s0, s1, _u32(site), jnp.uint32(salt))[0]


def uniform_at(s0, s1, site) -> jnp.ndarray:
    """u ~ U[0,1) at each ``site``: the top 24 bits of the U-stream draw,
    scaled — (bits >> 8) < 2^24 is exactly representable in float32, so
    the conversion is deterministic across executors.  It goes through
    int32 (exact below 2^31) because Mosaic has no uint32 -> float32
    cast."""
    bits = raw_draw(s0, s1, site, U_SALT)
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(
        1.0 / (1 << 24)
    )


def threshold_u32(p: float) -> int:
    """Static uint32 threshold with P(draw < threshold) = p."""
    return max(0, min(0xFFFFFFFF, int(round(float(p) * 4294967296.0))))


def flips_at(s0, s1, site, nbits: int, p_u32: int) -> jnp.ndarray:
    """Flip word at each ``site``: low ``nbits`` bit-planes i.i.d.
    Bernoulli(p), plane i from stream ``FLIP_SALT + i``."""
    word = jnp.zeros_like(_u32(site))
    for i in range(nbits):
        plane = raw_draw(s0, s1, site, FLIP_SALT + i) < jnp.uint32(p_u32)
        word = word | (plane.astype(jnp.uint32) << jnp.uint32(i))
    return word


def site_index(shape: tuple) -> jnp.ndarray:
    """Row-major linear site index over a per-chain state block."""
    n = 1
    for d in shape:
        n *= int(d)
    return jnp.arange(n, dtype=jnp.uint32).reshape(shape)
