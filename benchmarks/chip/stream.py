"""The fused stream contract, written out plainly for the references.

A draw for (chain, absolute step t, site s) is a pure function of the
chain key and the (t, s) counter (DESIGN.md, Randomness):

    chain key    key_c = fold_in(run key, chain)
    key words    (k0, k1) = key_data(key_c)
    step key     (s0, s1) = threefry2x32(k0, k1, (t, 0))
    draw         threefry2x32(s0, s1, (s, salt))[0]

The uniform is the top 24 bits of the ``u_salt`` draw over 2**24; flip
plane i of a proposal is ``draw(flip_salt + i) < round(p * 2**32)``.
Threefry-2x32-20 is Salmon et al., SC'11, with ``jax.random``'s rotation
schedule and parity constant.  Everything here is plain ``jax.numpy``
and imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
PARITY = 0x1BD11BDA


def threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32 with 20 rounds over broadcasting uint32 arrays."""
    k0, k1, x0, x1 = (jnp.asarray(a, jnp.uint32) for a in (k0, k1, x0, x1))

    def rotl(x, r):
        return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))

    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(PARITY))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def chain_words(run_key, chain: int = 0):
    """(k0, k1): the key words of chain ``chain`` of a run key."""
    words = jax.random.key_data(jax.random.fold_in(run_key, chain))
    return words[0], words[1]


def step_words(k0, k1, t):
    return threefry2x32(k0, k1, jnp.asarray(t).astype(jnp.uint32), 0)


def uniform(s0, s1, site, u_salt: int):
    bits = threefry2x32(s0, s1, site, u_salt)[0]
    return (bits >> jnp.uint32(8)).astype(jnp.int32).astype(
        jnp.float32
    ) * jnp.float32(1.0 / (1 << 24))


def flip_word(s0, s1, site, nbits: int, flip_salt: int, p: float):
    threshold = jnp.uint32(max(0, min(0xFFFFFFFF, round(p * 2.0**32))))
    word = jnp.zeros(jnp.shape(site), jnp.uint32)
    for i in range(nbits):
        plane = threefry2x32(s0, s1, site, flip_salt + i)[0] < threshold
        word = word | (plane.astype(jnp.uint32) << jnp.uint32(i))
    return word


def key_from_seed(seed: int):
    """A PRNG key from any whole-number seed (the low and high 32 bits
    both count)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0x7FFFFFFF
    )
