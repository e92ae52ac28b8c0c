"""Decode-position token sampling — the paper's technique in LLM decode.

Each decode step samples one token per row of a (B, V) logits table by
the engine's ``mh`` rule over ceil(log2 V)-bit words, one chain per row,
started at the row argmax (``MHEngine.sample_tokens``).  Words at or
past V are out of support and always rejected.

The logits are synthetic and drawn from a seed, as no model runs here:
each row is Zipf-ranked, the id of rank r holding the logit
``-s_b * ln(1 + r)`` with ``s_b ~ U[0.8, 1.4]`` and a seeded
permutation of the ids per row — a heavy-tailed next-token law, neither
flat nor one-hot.  At vocabulary width a near-uniform proposal lands
far down the ranks, so most chains keep the argmax for all 64 steps and
a few rows a step move.  Table ``j`` of a build is drawn from
``fold_in(key, j)``; the workload's own target is table 0, at
temperature 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import samplers

Array = jnp.ndarray

SLOPE = (0.8, 1.4)        # range of the per-row Zipf exponent s_b


@functools.partial(jax.jit, static_argnames=("batch", "vocab"))
def zipf_logits(key, batch: int, vocab: int) -> Array:
    """(batch, vocab) float32 Zipf-ranked logits from ``key``."""
    k_slope, k_perm = jax.random.split(key)
    s = jax.random.uniform(
        k_slope, (batch, 1), minval=SLOPE[0], maxval=SLOPE[1]
    )
    rank = jax.vmap(lambda k: jax.random.permutation(k, vocab))(
        jax.random.split(k_perm, batch)
    )
    return -s * jnp.log1p(rank.astype(jnp.float32))


def table_logits(key, table: int, batch: int, vocab: int) -> Array:
    """Logits table ``table`` of a build from ``key``."""
    return zipf_logits(jax.random.fold_in(key, table), batch, vocab)


def build(
    key,
    randomness: str = "cim",
    backend: str = "auto",
    smoke: bool = False,
    vocab: int | None = None,
    batch: int | None = None,
    n_steps: int | None = None,
    chunk_steps: int = 64,
    num_chains: int = 1,
    collect: str = "all",
):
    """Assemble the token-sampling workload (see workloads.WorkloadRun).

    ``batch`` is the decode batch (table rows), ``vocab`` the vocabulary
    width; the defaults are a decode step of 256 rows over DeepSeek-V3's
    129,280-token vocabulary, the smoke sizes 8 rows over 3,001 (not a
    power of two, wider than one 128-entry window of the kernel's
    lookup).  ``num_chains`` runs independent chains per row (DESIGN.md
    §Chains-axis), each started at the argmax.
    """
    from repro import workloads  # deferred: workloads imports this module

    vocab = vocab or (3001 if smoke else 129_280)
    batch = batch or (8 if smoke else 256)
    n_steps = n_steps or 64
    target = samplers.logits_target(table_logits(key, 0, batch, vocab))
    engine = samplers.MHEngine(
        samplers.EngineConfig(
            update="mh",
            randomness=randomness,
            execution=backend,
            chunk_steps=chunk_steps,
            num_chains=num_chains,
            collect=collect,
        )
    )
    init = jnp.argmax(target.table, axis=-1).astype(jnp.uint32)[:, None]
    if num_chains > 1:
        init = jnp.broadcast_to(init, (num_chains, *init.shape))

    def series_fn(samples: Array) -> Array:
        # (K, B, 1) words -> (K, B) token ids
        return samples.reshape(samples.shape[0], -1).astype(jnp.float32)

    return workloads.WorkloadRun(
        name="tokens",
        engine=engine,
        target=target,
        init_words=init,
        n_steps=n_steps,
        burn_in=n_steps // 4,
        series_fn=series_fn,
        meta={
            "vocab": vocab,
            "rows": batch,
            "num_chains": num_chains,
            "statistic": "token",
        },
    )
