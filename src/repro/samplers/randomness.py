"""Randomness axis of the sampler engine — where the MH random bits come from.

One MH step consumes two random operands per chain (paper Fig. 14):

  * a *flip word* whose low ``nbits`` bit-planes are i.i.d.
    Bernoulli(p_BFR) — the block-wise pseudo-read proposal, and
  * a uniform ``u`` in [0, 1) — the accurate-[0,1]-RNG accept threshold.

Three backends implement the same ``RandomnessBackend`` protocol
(DESIGN.md §Randomness):

  * ``HostRandomness``  — plain ``jax.random``: ideal float32 uniforms and
    directly-drawn Bernoulli bit-planes.  The software baseline.
  * ``CIMRandomness``   — the paper's circuit pipeline: biased pseudo-read
    bit-planes (``bitcell.raw_random_words``) for the proposal, and
    reset -> pseudo-read -> MSXOR-fold -> pack for ``u``
    (``uniform_rng.uniform``), including the residual debias error.
  * ``FusedRandomness`` — the paper's *placement*: the random bits are
    generated inside the thing doing the sampling.  Under pallas
    execution the fused kernels derive every operand in-kernel from a
    counter cipher (kernels/rng) keyed on ``(chain key, absolute step,
    site)`` — zero per-step operand traffic; this backend's ``step`` is
    the scan-side *reference* that draws the identical stream through
    the same shared functions, so {scan, pallas} stay bit-exact.

Chunked streaming contract (DESIGN.md §2): the operands for step ``t``
depend only on ``(key, t)`` — each backend's ``step`` draws them,
host/cim from the per-step key ``jax.random.fold_in(key, t)``, fused by
folding ``t`` into the counter cipher, and ``chunk`` is ``step`` vmapped
— so a chain may be generated in chunks of any size, or a step at a
time inside a scan body, and the resulting stream is *bit-identical* to
the monolithic (K, B, C) materialisation.  Long chains are therefore memory-bounded by the chunk
size, not the chain length.

One program per chunk (DESIGN.md §Randomness): every backend's
``chunk`` runs its ``draw`` body as one compiled program per ``(backend,
n_steps, shape, nbits, need_flips)``, with the key and ``start`` traced.
An eager caller (the Pallas chunk loop) pays one dispatch a chunk
instead of one per op; under a trace the body is inlined, so staged
programs are unchanged.  The stream is the body's, bit for bit.

Operand-lean mode (DESIGN.md §Collection): consumers that never read the
flip words — the Gibbs update rule draws no proposal, and the tempering
swap test needs only a uniform — pass ``need_flips=False`` and the
backend skips flip-plane generation entirely.  The u stream stays
*bit-identical* because every backend separates the operand streams
before drawing: host/cim split the step key into ``(k_flip, k_u)``,
fused salts the counter per operand — neither depends on whether the
flip stream was ever consumed (asserted in tests/test_collection.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import bitcell, uniform_rng
from repro.kernels import rng

Array = jnp.ndarray


def chain_key(key, chain_id) -> Array:
    """Counter-based per-chain key (DESIGN.md §Chains-axis).

    Every engine run — solo or multi-chain — derives its stream from
    ``fold_in(key, chain_id)`` and then per-step ``fold_in(·, t)``, so
    the operands for (chain c, step t) are a pure function of
    ``(key, c, t)``.  Chain c of a C-chain run is therefore bit-identical
    to a solo run launched with ``chain_id=c``, for any C.
    """
    return jax.random.fold_in(key, chain_id)


def chain_keys(key, num_chains: int, base: int = 0) -> Array:
    """Stacked per-chain keys for chains [base, base + num_chains)."""
    ids = base + jnp.arange(num_chains, dtype=jnp.int32)
    return jax.vmap(lambda c: chain_key(key, c))(ids)


@runtime_checkable
class RandomnessBackend(Protocol):
    """Produces the (flips, u) operand stream for a span of MH steps."""

    name: str

    def step(
        self, key, t, shape: tuple, nbits: int, need_flips: bool = True,
    ) -> tuple[Array | None, Array]:
        """Operands for the one absolute step ``t`` (may be traced):
        ``(flips shape uint32, u shape float32)``, or ``(None, u)`` under
        ``need_flips=False``.  A function of ``(key, t, shape)`` alone,
        so a scan step may draw its own operands where it uses them."""
        ...

    def chunk(
        self, key, start, n_steps: int, shape: tuple, nbits: int,
        need_flips: bool = True,
    ) -> tuple[Array | None, Array]:
        """Operands for steps [start, start+n_steps).

        Returns (flips (n_steps, *shape) uint32, u (n_steps, *shape)
        float32).  ``start`` may be a traced integer.
        ``need_flips=False`` skips flip-plane generation and returns
        ``(None, u)`` with a bit-identical u stream (the step key is
        split before either operand is drawn).
        """
        ...


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6), inline=True)
def _chunk_program(backend, key, start, n_steps, shape, nbits, need_flips):
    """One compiled program per ``(backend, n_steps, shape, nbits,
    need_flips)``: the key and ``start`` are traced, so every chunk of a
    run, and every later run, reuses it.  Called eagerly it is one
    dispatch; under a trace ``inline=True`` splices the body into the
    caller's jaxpr, so staged programs are the ones the body alone
    would give."""
    return backend.draw(key, start, n_steps, shape, nbits, need_flips)


def draw_cache_size() -> int:
    """Entries in the compiled draw's cache: grows iff a call compiled."""
    return _chunk_program._cache_size()


class _CompiledChunk:
    """The shared ``draw`` and ``chunk`` of the three backends.  ``draw``
    is their ``step`` vmapped over steps [start, start + n_steps), so a
    chunk holds exactly the operands the steps would draw one by one;
    ``chunk`` runs it compiled once per static signature
    (``_chunk_program``).  Backends are frozen dataclasses, so equal
    backends share the cache."""

    def draw(self, key, start, n_steps, shape, nbits, need_flips):
        ts = jnp.asarray(start, jnp.int32) + jnp.arange(
            n_steps, dtype=jnp.int32
        )
        return jax.vmap(
            lambda t: self.step(key, t, shape, nbits, need_flips)
        )(ts)

    def chunk(self, key, start, n_steps, shape, nbits, need_flips=True):
        return _chunk_program(
            self, key, start, int(n_steps), tuple(shape), int(nbits),
            bool(need_flips),
        )


@dataclasses.dataclass(frozen=True)
class HostRandomness(_CompiledChunk):
    """Ideal software randomness — the baseline the CIM pipeline replaces."""

    p_bfr: float = 0.45

    name = "host"

    def step(self, key, t, shape, nbits, need_flips=True):
        k_flip, k_u = jax.random.split(jax.random.fold_in(key, t))
        u = jax.random.uniform(k_u, shape, jnp.float32)
        if not need_flips:
            return None, u
        planes = jax.random.bernoulli(k_flip, self.p_bfr, (*shape, nbits))
        weights = (
            jnp.uint32(1) << jnp.arange(nbits, dtype=jnp.uint32)
        ).astype(jnp.uint32)
        flips = jnp.sum(
            planes.astype(jnp.uint32) * weights, axis=-1
        ).astype(jnp.uint32)
        return flips, u


@dataclasses.dataclass(frozen=True)
class CIMRandomness(_CompiledChunk):
    """Paper-faithful randomness: pseudo-read bit-planes + MSXOR uniforms."""

    p_bfr: float = 0.45            # proposal pseudo-read flip rate
    rng_p_bfr: float = 0.45        # [0,1]-RNG sub-array raw-bit bias
    rng_bit_width: int = 16        # packed debiased bits per uniform
    rng_stages: int = 3            # MSXOR fold stages

    name = "cim"

    def step(self, key, t, shape, nbits, need_flips=True):
        k_flip, k_u = jax.random.split(jax.random.fold_in(key, t))
        u = uniform_rng.uniform(
            k_u, shape, self.rng_p_bfr, self.rng_bit_width, self.rng_stages
        )
        if not need_flips:
            return None, u
        flips = bitcell.raw_random_words(
            k_flip, self.p_bfr, shape, nbits=nbits
        )
        return flips, u


@dataclasses.dataclass(frozen=True)
class FusedRandomness(_CompiledChunk):
    """In-kernel counter RNG — the scan-side reference stream.

    The stream contract (kernels/rng): operand for (chain, step t, site
    s) = Threefry-2x32 of the ``(t, s)`` counter under the chain key's
    two uint32 words, salted per operand.  Under pallas execution the
    fused kernels make exactly these draws *inside* the kernel — no
    operand tensors exist; this ``step`` draws the identical values
    through the same shared functions for the scan executors (and
    ``chunk`` for the tempering swap test), keeping the engine's bit-parity
    contract alive across {scan, pallas} (tests/test_fused_rng.py).
    """

    p_bfr: float = 0.45

    name = "fused"

    def step(self, key, t, shape, nbits, need_flips=True):
        s0, s1 = rng.step_key(*rng.key_words(key), t)
        site = rng.site_index(shape)
        u = rng.uniform_at(s0, s1, site)
        if not need_flips:
            return None, u
        p_u32 = rng.threshold_u32(self.p_bfr)
        return rng.flips_at(s0, s1, site, nbits, p_u32), u


def make_randomness_backend(
    name: str,
    p_bfr: float,
    rng_p_bfr: float | None = None,
    rng_bit_width: int = 16,
    rng_stages: int = 3,
) -> RandomnessBackend:
    if name == "host":
        return HostRandomness(p_bfr=p_bfr)
    if name == "cim":
        return CIMRandomness(
            p_bfr=p_bfr,
            rng_p_bfr=p_bfr if rng_p_bfr is None else rng_p_bfr,
            rng_bit_width=rng_bit_width,
            rng_stages=rng_stages,
        )
    if name == "fused":
        return FusedRandomness(p_bfr=p_bfr)
    raise ValueError(
        f"unknown randomness backend {name!r} (host|cim|fused)"
    )
