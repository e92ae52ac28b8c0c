"""Token-sampler fidelity/latency trade-off (the paper's technique in LLM
decode position): TV distance to the exact softmax distribution vs MH
steps, with and without the beyond-paper top-k restriction — plus the
engine's two new axes: scan vs fused-pallas execution (measured latency)
and host vs cim randomness (measured fidelity/acceptance delta)."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import samplers
from repro.core import token_sampler


def _sampler(cfg, logits):
    """One decode step of ``cfg`` over ``logits`` through
    ``MHEngine.sample_tokens``: (tokens, EngineResult) of a key."""
    engine = samplers.MHEngine(cfg.engine_config())
    return lambda k: engine.sample_tokens(
        k, logits, n_steps=cfg.n_steps, temperature=cfg.temperature,
        top_k=cfg.top_k,
    )


def _tv_for(cfg, logits, ref, n_runs=300, seed=0):
    step = _sampler(cfg, logits)
    counts = np.zeros(logits.shape[1])
    for k in jax.random.split(jax.random.PRNGKey(seed), n_runs):
        counts[int(step(k)[0][0])] += 1
    emp = counts / counts.sum()
    return float(0.5 * np.abs(emp - ref).sum())


def _latency_us(cfg, logits, reps=20, seed=0):
    step = _sampler(cfg, logits)
    keys = jax.random.split(jax.random.PRNGKey(seed), reps + 1)
    jax.block_until_ready(step(keys[0]))  # compile
    t0 = time.perf_counter()
    for k in keys[1:]:
        out = step(k)[0]
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run() -> list[dict]:
    rows = []
    vocab = 128
    n_runs = 300
    logits = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, vocab)) * 2.0, jnp.float32
    )
    ref_full = np.asarray(jax.nn.softmax(logits[0]))

    # finite-sample floor: n_runs draws from the exact softmax
    exact = np.asarray(
        jax.random.categorical(
            jax.random.PRNGKey(9), jnp.repeat(logits, n_runs, 0), axis=-1
        )
    )
    emp = np.bincount(exact, minlength=vocab) / n_runs
    rows.append(
        {
            "bench": "token_sampler_fidelity",
            "variant": "exact_categorical (finite-sample floor)",
            "mh_steps": "-",
            "tv_vs_reference": round(float(0.5 * np.abs(emp - ref_full).sum()), 4),
        }
    )

    for n_steps in (8, 32, 128, 512):
        cfg = token_sampler.TokenSamplerConfig(vocab_size=vocab, n_steps=n_steps)
        rows.append(
            {
                "bench": "token_sampler_fidelity",
                "variant": "full_vocab",
                "mh_steps": n_steps,
                "tv_vs_reference": round(_tv_for(cfg, logits, ref_full, n_runs), 4),
            }
        )
    for top_k in (8, 32):
        cfg = token_sampler.TokenSamplerConfig(
            vocab_size=vocab, n_steps=32, top_k=top_k
        )
        # compare against the *restricted* renormalised softmax the top-k
        # sampler actually targets
        top_vals, top_idx = jax.lax.top_k(logits[0], top_k)
        ref_k = np.zeros(vocab)
        ref_k[np.asarray(top_idx)] = np.asarray(jax.nn.softmax(top_vals))
        rows.append(
            {
                "bench": "token_sampler_fidelity",
                "variant": f"top_{top_k} (beyond-paper)",
                "mh_steps": 32,
                "tv_vs_reference": round(_tv_for(cfg, logits, ref_k, n_runs), 4),
            }
        )

    # --- randomness axis: host jax.random vs cim pseudo-read + MSXOR -----
    for randomness in ("host", "cim"):
        cfg = token_sampler.TokenSamplerConfig(
            vocab_size=vocab, n_steps=64, randomness=randomness
        )
        tv = _tv_for(cfg, logits, ref_full, n_runs)
        step = _sampler(cfg, logits)
        acc = float(np.mean([
            step(k)[1].acceptance_rate
            for k in jax.random.split(jax.random.PRNGKey(1), 32)
        ]))
        rows.append(
            {
                "bench": "token_sampler_randomness",
                "randomness": randomness,
                "mh_steps": 64,
                "tv_vs_reference": round(tv, 4),
                # canonical label + pre-rename alias (DESIGN.md §Run-API)
                "acceptance_rate": round(acc, 3),
                "acceptance": round(acc, 3),
            }
        )

    # --- execution axis: lax.scan vs fused pallas (interpret off-TPU) ----
    batch_logits = jnp.asarray(
        np.random.default_rng(1).normal(size=(8, vocab)) * 2.0, jnp.float32
    )
    on_tpu = jax.default_backend() == "tpu"
    for execution in ("scan", "pallas"):
        cfg = token_sampler.TokenSamplerConfig(
            vocab_size=vocab, n_steps=64, execution=execution
        )
        rows.append(
            {
                "bench": "token_sampler_backend",
                "execution": execution
                + ("" if on_tpu or execution == "scan" else " (interpret)"),
                "batch": batch_logits.shape[0],
                "mh_steps": 64,
                "us_per_batch": round(_latency_us(cfg, batch_logits), 1),
            }
        )
    return rows
