"""The probabilistic-model zoo (DESIGN.md §Workloads).

Every workload is the same three-piece contract riding the unified
sampler engine:

  * a **target** (log-prob table/callable for ``mh``, conditional lattice
    model for ``gibbs``),
  * an **update rule** + engine config (randomness/execution axes flow
    straight through, so every workload gets host-vs-cim and scan-vs-
    pallas for free),
  * a **scalar statistic** of the sample stream that
    ``repro.diagnostics`` judges (tau / ESS / split-R-hat).

``build(name, key, ...)`` assembles a ``WorkloadRun``; the registry is
what ``python -m repro.launch.sample`` and ``benchmarks.bench_workloads``
iterate over.  Adding a workload = one module exposing ``build`` plus a
registry line.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from repro import diagnostics, samplers
from repro.workloads import gmm, ising, spin_glass, tokens


@dataclasses.dataclass
class WorkloadRun:
    """One assembled workload: engine + target + chain layout + statistic."""

    name: str
    engine: samplers.MHEngine
    target: object
    init_words: object
    n_steps: int
    burn_in: int
    series_fn: Callable          # samples (K, *chain) -> (K, n_chains) stat
    meta: dict

    def plan(self, key, mesh=None, **overrides) -> samplers.RunPlan:
        """The workload's ``RunPlan`` (DESIGN.md §Run-API) — the spec
        ``run`` submits; callers needing resume/checkpoint semantics take
        this and drive it themselves (e.g. checkpoint.run_resumable)."""
        spec = dict(
            target=self.target,
            n_steps=self.n_steps,
            init_words=self.init_words,
            key=key,
            mesh=mesh,
        )
        spec.update(overrides)
        return samplers.RunPlan(**spec)

    def run(self, key, mesh=None) -> samplers.EngineResult:
        """Run the chains; ``mesh`` shards the engine's chains axis
        (DESIGN.md §Chains-axis) and is a no-op for solo runs."""
        return self.engine.submit(self.plan(key, mesh=mesh)).result

    def series(self, result: samplers.EngineResult) -> np.ndarray:
        """(T, n_columns) scalar-statistic block; a multi-chain run's
        chains contribute their columns side by side."""
        num_chains = self.engine.config.num_chains
        if num_chains == 1:
            series = np.asarray(self.series_fn(result.samples))
            return series.reshape(series.shape[0], -1)
        cols = [
            np.asarray(self.series_fn(result.samples[c])).reshape(
                result.samples.shape[1], -1
            )
            for c in range(num_chains)
        ]
        return np.concatenate(cols, axis=1)

    @property
    def rate_key(self) -> str:
        """THE canonical label for the engine's accept/flip rate — Gibbs
        has no reject, so its count is a flip count (DESIGN.md §2):
        ``acceptance_rate`` for mh, ``flip_rate`` for gibbs.  Diagnostics,
        the CLI, and the bench tables all spell it through here (bench
        rows keep a legacy ``acceptance`` alias column for old readers)."""
        return (
            "flip_rate" if self.engine.config.update == "gibbs"
            else "acceptance_rate"
        )

    def rate_entry(self, result: samplers.EngineResult) -> tuple[str, float]:
        """(canonical label, value) for the engine's accept/flip rate."""
        return self.rate_key, round(float(result.acceptance_rate), 4)

    # pre-rename spelling, kept for external callers
    _rate_entry = rate_entry

    def kept_burn_in(self) -> int:
        """``burn_in`` translated to the collected stream's row index:
        under ``thin:k`` the kept steps (step0 = 0) are t = 0, k, 2k, …,
        so ceil(burn_in / k) kept rows fall inside the burn-in window."""
        mode, k = samplers.parse_collect(self.engine.config.collect)
        if mode == "thin":
            return -(-self.burn_in // k)
        return self.burn_in

    def diagnostics(self, result: samplers.EngineResult) -> dict:
        """Chain diagnostics over the post-burn-in scalar statistic.

        Multi-chain runs feed the (T, C·m) block through
        ``diagnostics.StreamingChainStats`` in ``chunk_steps``-sized
        chunks.  Here the block already sits in host memory (the engine
        collects every state), so this exercises the streaming
        estimators' contract on every run rather than saving memory; the
        O(chunk) benefit is realised by producers that feed the
        accumulator chunk-by-chunk without materialising T (see
        DESIGN.md §Chains-axis).

        The collection axis flows through (DESIGN.md §Collection): under
        ``thin:k`` the estimators consume the kept stream (burn-in
        translated to kept rows — note tau/ESS then measure the *thinned*
        series); under ``last`` there is no series, so only the
        accept/flip rate is reported.
        """
        mode, _ = samplers.parse_collect(self.engine.config.collect)
        if mode == "last":
            label, value = self._rate_entry(result)
            return {"n_steps": 0, label: value}
        series = self.series(result)[self.kept_burn_in():]
        if self.engine.config.num_chains == 1:
            out = diagnostics.summarize(
                series, acceptance_rate=float(result.acceptance_rate)
            )
        else:
            chunk = max(1, self.engine.config.chunk_steps)
            out = diagnostics.summarize_stream(
                (
                    series[s : s + chunk]
                    for s in range(0, series.shape[0], chunk)
                ),
                num_chains=series.shape[1],
                total_steps=series.shape[0],
                acceptance_rate=float(result.acceptance_rate),
            )
        # Gibbs has no reject — the engine's accept_count is a flip
        # count (DESIGN.md §2); _rate_entry owns the label rule
        label, _ = self._rate_entry(result)
        if label != "acceptance_rate":
            out[label] = out.pop("acceptance_rate")
        return out


WORKLOADS = {
    "ising": ising.build,
    "gmm": gmm.build,
    "spin_glass": spin_glass.build,
    "tokens": tokens.build,
}


def build(name: str, key, **kwargs) -> WorkloadRun:
    """Assemble a registered workload by name."""
    try:
        builder = WORKLOADS[name]
    except KeyError:
        raise ValueError(
            f"unknown workload {name!r} (have {sorted(WORKLOADS)})"
        ) from None
    return builder(key, **kwargs)
