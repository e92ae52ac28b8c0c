"""ising-1024: the plain reference and the work counts of the deployment.

Reference: checkerboard Gibbs on periodic H x W lattices, written from
the model's definition (p(s_i = +1 | neighbours) = sigmoid(2 (beta *
sum_j s_j + h)), colour (row + col) % 2 == t % 2 updates at half-sweep
t) and the fused stream contract (``stream.py``).  It runs on the
device in plain ``jax.numpy``, one lattice block at a time, and imports
nothing of the program.  ``precision="bfloat16"`` computes the
conditional in bfloat16: the control, which has to fail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import stream

# Sites the reference advances at once: whole lattices, as many as fit.
BLOCK_SITES = 2**24


def builder_kwargs(cfg: dict) -> dict:
    """What ``workloads.build`` takes from this configuration."""
    return dict(height=cfg["height"], width=cfg["width"], beta=cfg["beta"],
                field=cfg["field"])


# --- work: what a job has to do ---------------------------------------------


def sites(cfg: dict, job: dict) -> int:
    return int(job["batch"]) * cfg["height"] * cfg["width"]


def job_updates(cfg: dict, job: dict) -> int:
    """Site-steps: every site of every lattice, once per half-sweep."""
    return sites(cfg, job) * int(job["n_steps"])


def job_blocks(cfg: dict, job: dict, randomness: str) -> int | None:
    """Threefry blocks the fused stream contract requires: one uniform
    for each site of the active colour per half-sweep, and one step key
    per half-sweep.  None where the randomness is not the fused cipher."""
    if randomness != "fused":
        return None
    n = int(job["n_steps"])
    return sites(cfg, job) // 2 * n + n


def job_bytes(cfg: dict, job: dict) -> int:
    """Bytes a job has to move through HBM at least: the initial words
    in; the final words, per-site flip counts and per-site log-probs
    out, 4 bytes each (collect="last" keeps no rows)."""
    return 16 * sites(cfg, job)


# --- reference ----------------------------------------------------------------


def init_words(cfg: dict, build_key, batch: int):
    """The chain's initial lattices: fair spins from fold_in(key, 0)."""
    k = jax.random.fold_in(build_key, 0)
    return jax.random.bernoulli(
        k, 0.5, (batch, cfg["height"], cfg["width"])
    ).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("precision", "u_salt"))
def _sweeps(state, site, k0, k1, n_steps, beta, field, *, precision: str,
            u_salt: int):
    h, w = state.shape[-2:]
    row = jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)
    colour = (row + col) % 2

    def body(t, carry):
        s, flips = carry
        s0, s1 = stream.step_words(k0, k1, t)
        u = stream.uniform(s0, s1, site, u_salt)
        spin = 2.0 * s.astype(jnp.int32).astype(jnp.float32) - 1.0
        nb = (jnp.roll(spin, 1, -2) + jnp.roll(spin, -1, -2)
              + jnp.roll(spin, 1, -1) + jnp.roll(spin, -1, -1))
        logit = 2.0 * (beta * nb + field)
        if precision == "bfloat16":
            p = jax.nn.sigmoid(logit.astype(jnp.bfloat16)).astype(jnp.float32)
        else:
            p = jax.nn.sigmoid(logit)
        new = (u < p).astype(jnp.uint32)
        nxt = jnp.where(colour == t % 2, new, s)
        return nxt, flips + (nxt != s).astype(jnp.int32)

    return jax.lax.fori_loop(
        0, n_steps, body, (state, jnp.zeros(state.shape, jnp.int32))
    )


def reference(cfg: dict, case: dict, precision: str = "float32") -> dict:
    """Final words and per-site flip counts of one job or request.

    ``case`` names the build key (for the initial lattices), the run key
    (whose chain 0 streams the uniforms), the lattices and the steps."""
    batch, n = int(case["batch"]), int(case["n_steps"])
    init = init_words(cfg, case["build_key"], batch)
    k0, k1 = stream.chain_words(case["run_key"])
    per = cfg["height"] * cfg["width"]
    block = max(1, BLOCK_SITES // per)
    finals, flips = [], []
    for b in range(0, batch, block):
        nb = min(block, batch - b)
        site = (
            jnp.arange(nb * per, dtype=jnp.uint32) + jnp.uint32(b * per)
        ).reshape(nb, cfg["height"], cfg["width"])
        f, c = _sweeps(
            init[b:b + nb], site, k0, k1, n, jnp.float32(cfg["beta"]),
            jnp.float32(cfg["field"]), precision=precision,
            u_salt=cfg["stream"]["u_salt"],
        )
        finals.append(np.asarray(f))
        flips.append(np.asarray(c))
    return {"final_words": np.concatenate(finals),
            "accept_count": np.concatenate(flips)}


def compare(cfg: dict, case: dict, out: dict, ref: dict | None = None) -> dict:
    """Numbers compared for one job or request: final sites that differ
    from the reference, and sites whose flip count differs."""
    ref = reference(cfg, case) if ref is None else ref
    final = np.asarray(out["final_words"]).reshape(ref["final_words"].shape)
    count = np.asarray(out["accept_count"]).reshape(ref["accept_count"].shape)
    return {
        "sites_off": int(np.count_nonzero(final != ref["final_words"])),
        "flips_off": int(np.count_nonzero(count != ref["accept_count"])),
    }
