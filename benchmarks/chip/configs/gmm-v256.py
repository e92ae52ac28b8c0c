"""gmm-v256: the plain reference and the work counts of the deployment.

Reference: Metropolis-Hastings over the 2**nbits cells of the paper's
Fig. 17(a) mixture, written from the definitions: the target table is
the mixture's log density at each cell centre, computed here in float64
from the configuration's means, variances and weights; a step proposes
``x ^ (flip & mask)`` and accepts when ``u < exp(min(dlogp, 0))``.  The
operands follow the randomness the job names:

* ``fused``: the counter cipher of ``stream.py``;
* ``cim``: per absolute step t, ``(k_flip, k_u) = split(fold_in(chain
  key, t))``; the flip word packs nbits Bernoulli(p_bfr) planes drawn
  from k_flip, and u packs ``rng_bit_width`` bits, each the XOR of
  2**rng_stages Bernoulli(rng_p_bfr) draws from k_u, over
  2**rng_bit_width (the paper's pseudo-read and MSXOR pipeline).

Every kept sample is judged on its own: the reference applies its step
to the chain's previous state, as the job returned it, and counts the
steps whose result differs.  A step whose uniform lies within
``rounding_band`` of the acceptance ratio is left to the program: the
device evaluates the mixture's log density in float32, the reference
in float64, and a few units in the last place of the table move such
a decision (PERF.md gives the readings).  Nothing of the program is imported.
``precision="bfloat16"`` computes table and acceptance in bfloat16:
the control, which has to fail.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import stream

# Steps whose operands the reference draws at once.
BLOCK = {"fused": 128, "cim": 8}


def builder_kwargs(cfg: dict) -> dict:
    """What ``workloads.build`` takes from this configuration."""
    return dict(nbits=cfg["nbits"], chains=cfg["chains"])


# --- work: what a job has to do ---------------------------------------------


def job_updates(cfg: dict, job: dict) -> int:
    """Chain-steps."""
    return int(cfg["chains"]) * int(job["n_steps"])


def job_blocks(cfg: dict, job: dict, randomness: str) -> int | None:
    """Threefry blocks the fused stream contract requires per chain-step:
    one step key, nbits flip planes and one uniform.  None where the
    randomness is not the fused cipher."""
    if randomness != "fused":
        return None
    return job_updates(cfg, job) * (2 + int(cfg["nbits"]))


def job_bytes(cfg: dict, job: dict) -> int:
    """Bytes a job has to move through HBM at least: the table and the
    initial words in; every kept row, and the final words, accept
    counts and log-probs out, 4 bytes each."""
    c, n = int(cfg["chains"]), int(job["n_steps"])
    kept = n if job["collect"] == "all" else 0
    return 4 * (1 << int(cfg["nbits"])) + 4 * c * (4 + kept)


# --- reference ----------------------------------------------------------------


def table(cfg: dict) -> np.ndarray:
    """log p at each cell centre, float64 -> float32, shape (2**nbits,)."""
    v = 1 << int(cfg["nbits"])
    x = cfg["lo"] + (np.arange(v) + 0.5) / v * (cfg["hi"] - cfg["lo"])
    comps = [
        math.log(w) - 0.5 * (x - m) ** 2 / s2 - 0.5 * math.log(2 * math.pi * s2)
        for m, s2, w in zip(cfg["means"], cfg["variances"], cfg["weights"])
    ]
    top = np.max(comps, axis=0)
    logp = top + np.log(np.sum([np.exp(c - top) for c in comps], axis=0))
    return logp.astype(np.float32)


def init_words(cfg: dict, build_key):
    """The chains' initial words: randint(fold_in(key, 0), (1, C))."""
    k = jax.random.fold_in(build_key, 0)
    return jax.random.randint(
        k, (1, int(cfg["chains"])), 0, 1 << int(cfg["nbits"]), dtype=jnp.int32
    ).astype(jnp.uint32)


def _pack(bits, nbits: int):
    weights = jnp.uint32(1) << jnp.arange(nbits, dtype=jnp.uint32)
    return jnp.sum(bits.astype(jnp.uint32) * weights, axis=-1).astype(
        jnp.uint32
    )


@functools.partial(jax.jit, static_argnames=("n", "shape", "randomness",
                                              "nbits", "p", "salts", "cim"))
def _operands(k0, k1, run_key, t0, *, n: int, shape: tuple, randomness: str,
              nbits: int, p: float, salts: tuple, cim: tuple):
    """(flips, u), each (n, *shape), for absolute steps t0 .. t0 + n."""
    ts = t0 + jnp.arange(n, dtype=jnp.int32)
    if randomness == "fused":
        site = jnp.arange(math.prod(shape), dtype=jnp.uint32).reshape(shape)

        def one(t):
            s0, s1 = stream.step_words(k0, k1, t)
            return (stream.flip_word(s0, s1, site, nbits, salts[1], p),
                    stream.uniform(s0, s1, site, salts[0]))

        return jax.vmap(one)(ts)
    chain = jax.random.fold_in(run_key, 0)
    rng_p, width, stages = cim

    def one(t):
        k_flip, k_u = jax.random.split(jax.random.fold_in(chain, t))
        flips = _pack(jax.random.bernoulli(k_flip, p, (*shape, nbits)), nbits)
        raw = jax.random.bernoulli(k_u, rng_p, (*shape, 1 << stages, width))
        bits = jax.lax.reduce(raw, False, jnp.logical_xor, (len(shape),))
        u = _pack(bits, width).astype(jnp.float32) / jnp.float32(1 << width)
        return flips, u

    return jax.vmap(one)(ts)


@functools.partial(jax.jit, static_argnames=("nbits", "precision"))
def _steps(prev, flips, u, tbl, *, nbits: int, precision: str):
    """The reference's step from each previous state: (next, accepted,
    candidate, acceptance ratio)."""
    cand = prev ^ (flips & jnp.uint32((1 << nbits) - 1))
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    t = tbl.astype(dtype)
    lp, lp_c = t[prev.astype(jnp.int32)], t[cand.astype(jnp.int32)]
    ratio = jnp.exp(jnp.minimum(lp_c - lp, jnp.zeros((), dtype))).astype(
        jnp.float32)
    accept = (u < ratio) & jnp.isfinite(lp_c)
    return jnp.where(accept, cand, prev), accept, cand, ratio


@functools.partial(jax.jit, static_argnames=("nbits", "band"))
def _judge(prev, got, flips, u, tbl, *, nbits: int, band: float):
    """(steps off, accepted per chain) of kept states ``got`` against the
    reference's step from ``prev``.  A step whose uniform lies within
    ``band`` of the reference's acceptance ratio is decided by rounding
    of the target table; there the program's decision stands, if it
    made one of the two possible moves."""
    nxt, accept, cand, ratio = _steps(prev, flips, u, tbl, nbits=nbits,
                                      precision="float32")
    differs = nxt != got
    moved = (got == prev) | (got == cand)
    excused = differs & moved & (jnp.abs(u - ratio) <= band)
    took = (got != prev) | (cand == prev)
    accepted = jnp.where(excused, took, accept)
    off = jnp.sum(differs & ~excused, dtype=jnp.int32)
    return off, jnp.sum(accepted, axis=0, dtype=jnp.int32)


def _stream_args(cfg: dict, randomness: str):
    return dict(
        randomness=randomness, nbits=int(cfg["nbits"]), p=float(cfg["p_bfr"]),
        salts=(cfg["stream"]["u_salt"], cfg["stream"]["flip_salt"]),
        cim=(float(cfg["cim"]["rng_p_bfr"]), int(cfg["cim"]["rng_bit_width"]),
             int(cfg["cim"]["rng_stages"])),
    )


def compare(cfg: dict, case: dict, out: dict) -> dict:
    """Numbers compared for one job: steps whose kept state differs from
    the reference's step from the previous kept state, outside the
    rounding band (the final words count as one more step), and chains
    whose accept count differs."""
    samples = jnp.asarray(out["samples"])            # (n, 1, C)
    n = int(case["n_steps"])
    if samples.shape[0] != n:
        raise ValueError(f"kept {samples.shape[0]} rows of {n} steps")
    shape = tuple(samples.shape[1:])
    tbl = jnp.asarray(table(cfg))
    k0, k1 = stream.chain_words(case["run_key"])
    args = _stream_args(cfg, case["randomness"])
    nbits = args["nbits"]
    block = BLOCK[case["randomness"]]
    prev0 = init_words(cfg, case["build_key"])
    off = 0
    accepted = jnp.zeros(shape, jnp.int32)
    for t0 in range(0, n, block):
        m = min(block, n - t0)
        flips, u = _operands(k0, k1, case["run_key"], jnp.int32(t0), n=m,
                             shape=shape, **args)
        prev = jnp.concatenate([prev0[None], samples[t0:t0 + m - 1]])
        n_off, acc = _judge(prev, samples[t0:t0 + m], flips, u, tbl,
                            nbits=nbits, band=float(cfg["rounding_band"]))
        off += int(n_off)
        accepted = accepted + acc
        prev0 = samples[t0 + m - 1]
    final = jnp.asarray(out["final_words"]).reshape(shape)
    off += int(jnp.count_nonzero(final != samples[-1]))
    count = jnp.asarray(out["accept_count"]).reshape(shape)
    return {"steps_off": off,
            "accepts_off": int(jnp.count_nonzero(count != accepted))}


def reference(cfg: dict, case: dict, precision: str = "float32") -> dict:
    """The reference run forward, in the program's place: kept samples,
    final words and accept counts of one job (the control's path)."""
    n = int(case["n_steps"])
    state = init_words(cfg, case["build_key"])
    shape = tuple(state.shape)
    tbl = jnp.asarray(table(cfg))
    k0, k1 = stream.chain_words(case["run_key"])
    args = _stream_args(cfg, case["randomness"])
    block = BLOCK[case["randomness"]]
    rows, accepted = [], jnp.zeros(shape, jnp.int32)
    for t0 in range(0, n, block):
        m = min(block, n - t0)
        flips, u = _operands(k0, k1, case["run_key"], jnp.int32(t0), n=m,
                             shape=shape, **args)
        for i in range(m):
            state, acc, _, _ = _steps(state, flips[i], u[i], tbl,
                                      nbits=args["nbits"],
                                      precision=precision)
            rows.append(state)
            accepted = accepted + acc.astype(jnp.int32)
    return {"samples": jnp.stack(rows), "final_words": state,
            "accept_count": accepted}
