"""Softmax-free MCMC token sampling — the paper's technique in LLM decode.

The next-token id is treated as a ceil(log2 V)-bit word.  The proposal flips
each bit with p_BFR (the pseudo-read analogue); u comes from the MSXOR
debiased uniform RNG; the accept test uses only the *logit difference*
exp((l* - l)/T) — exactly the paper's alpha = p(x*)/p(x^(i)) simplification.
No logsumexp over the vocabulary is ever computed.

Out-of-vocab proposals (V is rarely a power of two) have p = 0 and are
always rejected, which preserves detailed balance restricted to [0, V).

This module is an API-compatible wrapper over the unified sampler engine
(``repro.samplers``): the chain itself lives there once, and the
``execution`` / ``randomness`` fields select the lax.scan vs fused-Pallas
executor and the host vs CIM randomness pipeline (DESIGN.md §2).

Statistical behaviour: with p_BFR ~ 0.45 the proposal is a near-uniform
independence sampler over the 2^k hypercube, so the chain mixes in O(1/p_max)
steps for heavy-tailed targets and benefits from temperature warm-up for
peaked ones.  ``n_steps`` and the top-k restriction (beyond-paper option)
trade fidelity for latency; fidelity is quantified in
benchmarks/bench_token_sampler.py.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import NamedTuple

import jax.numpy as jnp

from repro import samplers

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class TokenSamplerConfig:
    vocab_size: int
    n_steps: int = 64                 # MH iterations per emitted token
    p_bfr: float = 0.45
    rng_bit_width: int = 24           # u precision (logit ratios can be tiny)
    rng_stages: int = 3
    temperature: float = 1.0
    top_k: int = 0                    # 0 = full vocab (paper-faithful);
                                      # >0 restricts the chain to top-k logits
    execution: str = "auto"           # auto | scan | pallas (engine dispatch)
    randomness: str = "cim"           # cim | host randomness backend
    chunk_steps: int = 64             # randomness streaming granularity

    @property
    def nbits(self) -> int:
        space = self.top_k if self.top_k > 0 else self.vocab_size
        return max(1, math.ceil(math.log2(space)))

    def engine_config(self) -> samplers.EngineConfig:
        return samplers.EngineConfig(
            p_bfr=self.p_bfr,
            randomness=self.randomness,
            rng_p_bfr=self.p_bfr,
            rng_bit_width=self.rng_bit_width,
            rng_stages=self.rng_stages,
            execution=self.execution,
            chunk_steps=self.chunk_steps,
        )


class TokenSampleResult(NamedTuple):
    tokens: Array            # (batch,) int32 sampled token ids
    acceptance_rate: Array   # scalar float32
    final_logp: Array        # (batch,) float32 unnormalised log-prob


def _sample_tokens_impl(
    key,
    logits: Array,
    cfg: TokenSamplerConfig,
    init_tokens: Array | None = None,
) -> TokenSampleResult:
    """One decode step through ``MHEngine.sample_tokens``: from the host
    one compiled program per (config, shapes), the logits traced; inside
    a caller's trace, staged into it."""
    engine = samplers.MHEngine(cfg.engine_config())
    tokens, result = engine.sample_tokens(
        key,
        logits,
        n_steps=cfg.n_steps,
        temperature=cfg.temperature,
        top_k=cfg.top_k,
        init_tokens=init_tokens,
    )
    return TokenSampleResult(
        tokens=tokens,
        acceptance_rate=result.acceptance_rate,
        final_logp=result.final_logp[:, 0],
    )


def sample_tokens(
    key,
    logits: Array,
    cfg: TokenSamplerConfig,
    init_tokens: Array | None = None,
) -> TokenSampleResult:
    """Draw one token per row of ``logits`` (B, V) via the CIM-MCMC chain.

    ``init_tokens`` seeds each chain (e.g. the previous sampled token —
    the macro's "initial value x^(0) written into the bitcells"); defaults
    to the argmax, which guarantees a finite-logp start.

    .. deprecated:: the documented surface is
       ``MHEngine.sample_tokens`` reached through ``repro.samplers``
       (DESIGN.md §Run-API); this wrapper stays bit-compatible.
    """
    warnings.warn(
        "core.token_sampler.sample_tokens is deprecated; configure an "
        "MHEngine via repro.samplers and call engine.sample_tokens "
        "(DESIGN.md §Run-API)",
        DeprecationWarning,
        stacklevel=2,
    )
    return _sample_tokens_impl(key, logits, cfg, init_tokens)
