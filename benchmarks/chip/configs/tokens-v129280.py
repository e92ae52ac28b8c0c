"""tokens-v129280: the plain reference and the work counts of the deployment.

Reference: one Metropolis-Hastings chain per row of a (B, V) logits
table, written from the definitions.  The table is drawn from the
configuration's ``logits`` recipe (Zipf-ranked rows, a seeded
permutation of the ids per row) and divided by the temperature; each
chain starts at its row's argmax; a step proposes ``w ^ (flip & mask)``
over ``nbits``-bit words, gives a proposal at or past V the log-prob
-inf, and accepts when ``u < exp(min(dlogp, 0))`` and the proposal's
log-prob is finite.  Flip words and uniforms follow the fused stream
contract (``stream.py``) at site b, the row, under the chain key
``fold_in(run key, 0)``.  The final token is the final word, and the
final log-prob the table entry it holds.

It runs in float32 under ``jax.default_matmul_precision("highest")``, a
block of rows at a time, and imports nothing of the program.
``precision="bfloat16"`` keeps the table and the acceptance ratio in
bfloat16: the control, which has to fail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import stream

# Rows the reference advances at once.
BLOCK_ROWS = 64
# Relative difference past which a final log-prob is off: a few float32
# units in the last place; bfloat16 keeps 8 bits (2**-9 apart).
LOGP_BAND = 1e-6


def builder_kwargs(cfg: dict) -> dict:
    """What ``workloads.build`` takes from this configuration."""
    return dict(vocab=cfg["vocab"], batch=cfg["batch"])


# --- work: what a job has to do ---------------------------------------------


def job_updates(cfg: dict, job: dict) -> int:
    """Chain-steps: one chain a row."""
    return int(cfg["batch"]) * int(job["n_steps"])


def job_blocks(cfg: dict, job: dict, randomness: str) -> int | None:
    """Threefry blocks the fused stream contract requires: nbits flip
    planes and one uniform per chain-step, and one step key per step
    (every row shares the chain key).  None where the randomness is not
    the fused cipher."""
    if randomness != "fused":
        return None
    n = int(job["n_steps"])
    return job_updates(cfg, job) * (int(cfg["nbits"]) + 1) + n


def job_bytes(cfg: dict, job: dict) -> int:
    """Bytes a decode step has to move through HBM at least: one read of
    the (B, V) float32 logits (the argmax and the temperature need every
    entry); the tokens, final words and accept counts are 4 bytes a row
    each."""
    b = int(cfg["batch"])
    return 4 * b * int(cfg["vocab"]) + 12 * b


def kernel_blocks(cfg: dict, job: dict) -> int:
    """Threefry blocks of the vocabulary kernel: all of the job's."""
    return job_blocks(cfg, job, "fused")


def kernel_bytes(cfg: dict, job: dict) -> int:
    """Bytes the vocabulary kernel has to move at least: the initial
    word in, one table entry per chain-step, and the final word and
    accept count out, 4 bytes each."""
    b, n = int(cfg["batch"]), int(job["n_steps"])
    return 4 * b * (n + 3)


# --- reference ----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("batch", "vocab", "lo", "hi"))
def _logits(key, *, batch: int, vocab: int, lo: float, hi: float):
    k_slope, k_perm = jax.random.split(key)
    slope = jax.random.uniform(k_slope, (batch, 1), minval=lo, maxval=hi)
    rank = jax.vmap(lambda k: jax.random.permutation(k, vocab))(
        jax.random.split(k_perm, batch))
    return -slope * jnp.log1p(rank.astype(jnp.float32))


def logits(cfg: dict, build_key, table: int):
    """Table ``table`` of the deployment: (batch, vocab) float32."""
    lo, hi = cfg["logits"]["slope"]
    return _logits(jax.random.fold_in(build_key, table),
                   batch=int(cfg["batch"]), vocab=int(cfg["vocab"]),
                   lo=float(lo), hi=float(hi))


@functools.partial(jax.jit, static_argnames=(
    "n_steps", "nbits", "p", "salts", "precision"))
def _chains(tbl, first, k0, k1, *, n_steps: int, nbits: int, p: float,
            salts: tuple, precision: str):
    """Final words, accept counts and final log-probs of one chain per
    row of ``tbl``, a block of rows whose first is row ``first`` of the
    table (the site of a row's stream is its row)."""
    rows, vocab = tbl.shape
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    t = tbl.astype(dtype)
    site = first + jnp.arange(rows, dtype=jnp.uint32)
    mask = jnp.uint32((1 << nbits) - 1)

    def log_prob(words):
        idx = jnp.minimum(words, jnp.uint32(vocab - 1)).astype(jnp.int32)
        vals = jnp.take_along_axis(t, idx[:, None], axis=1)[:, 0]
        return jnp.where(words < vocab, vals, jnp.array(-jnp.inf, dtype))

    def body(step, carry):
        w, lp, acc = carry
        s0, s1 = stream.step_words(k0, k1, step)
        flip = stream.flip_word(s0, s1, site, nbits, salts[1], p)
        u = stream.uniform(s0, s1, site, salts[0])
        cand = w ^ (flip & mask)
        lp_c = log_prob(cand)
        ratio = jnp.exp(jnp.minimum(lp_c - lp, jnp.zeros((), dtype))).astype(
            jnp.float32)
        ok = (u < ratio) & jnp.isfinite(lp_c)
        return (jnp.where(ok, cand, w), jnp.where(ok, lp_c, lp),
                acc + ok.astype(jnp.int32))

    w0 = jnp.argmax(tbl, axis=1).astype(jnp.uint32)
    w, lp, acc = jax.lax.fori_loop(
        0, n_steps, body, (w0, log_prob(w0), jnp.zeros((rows,), jnp.int32)))
    return w, acc, lp.astype(jnp.float32)


def reference(cfg: dict, case: dict, precision: str = "float32") -> dict:
    """Final words, accept counts and final log-probs of one decode step
    over table ``case["table"]`` under the chain key of
    ``case["run_key"]``."""
    table = logits(cfg, case["build_key"], int(case["table"]))
    table = table / jnp.float32(cfg["temperature"])
    k0, k1 = stream.chain_words(case["run_key"])
    args = dict(n_steps=int(case["n_steps"]), nbits=int(cfg["nbits"]),
                p=float(cfg["p_bfr"]),
                salts=(cfg["stream"]["u_salt"], cfg["stream"]["flip_salt"]),
                precision=precision)
    words, counts, logps = [], [], []
    with jax.default_matmul_precision("highest"):
        for b in range(0, table.shape[0], BLOCK_ROWS):
            w, acc, lp = _chains(table[b:b + BLOCK_ROWS], jnp.uint32(b), k0,
                                 k1, **args)
            words.append(np.asarray(w))
            counts.append(np.asarray(acc))
            logps.append(np.asarray(lp))
    words = np.concatenate(words)
    return {"tokens": words.astype(np.int32), "final_words": words,
            "accept_count": np.concatenate(counts),
            "final_logp": np.concatenate(logps)}


def compare(cfg: dict, case: dict, out: dict) -> dict:
    """Numbers compared for one decode step: rows whose sampled token
    differs from the reference's, rows whose accept count differs, and
    rows whose final log-prob lies more than ``LOGP_BAND`` (relative)
    from the reference's.  Most rows keep the argmax, whose log-prob is
    0 at any precision; the rows that move carry the precision check."""
    ref = reference(cfg, case)
    tokens = np.asarray(out["tokens"]).reshape(-1)
    count = np.asarray(out["accept_count"]).reshape(-1)
    logp = np.asarray(out["final_logp"], np.float32).reshape(-1)
    gap = np.abs(logp - ref["final_logp"])
    return {
        "tokens_off": int(np.count_nonzero(tokens != ref["tokens"])),
        "accepts_off": int(np.count_nonzero(count != ref["accept_count"])),
        "logp_off": int(np.count_nonzero(
            ~(gap <= LOGP_BAND * np.abs(ref["final_logp"])))),
    }
