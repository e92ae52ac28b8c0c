"""Measured engine constants: the per-(workload, shape, device) autotuner.

``EngineConfig.chunk_steps`` / ``block_c`` / ``execution`` were
hand-chosen constants — right for the machine they were tuned on, wrong
everywhere else.  This module measures them the way the bench harness
does (warm-up compile, then best-of-N wall-clock on a short run —
benchmarks/bench_workloads.py) and caches the winner per

    (update rule, randomness, target kind, state shape/dtype,
     num_chains, collect, platform, device kind, device count)

so a given workload shape pays the measurement once per machine.  The
candidate grid ALWAYS contains the incumbent config, and the winner is
the measured argmax — so a tuned config is never slower than the
hand-chosen constants *under the tuner's own measurement protocol* (the
bench-gate guarantee, benchmarks/bench_autotune.py).

Chunking and executor choice never change the sample stream (DESIGN.md
§2: operands are keyed on absolute step; scan and pallas mirror each
other op-for-op), so tuning is free to move them between runs — even
across a checkpoint/resume boundary (checkpoint/resume.py excludes them
from the resume fingerprint for exactly this reason).

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune.json``.  Writes are atomic (tmp + rename),
mirroring the checkpoint subsystem's durability idiom.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import jax

from repro import telemetry
from repro.samplers.engine import (
    EngineConfig,
    IneligibleExecution,
    MHEngine,
    resolve_execution,
)
from repro.samplers.plan import RunPlan

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
CACHE_VERSION = 1

# Small by design: each candidate costs one compile.  Callers with
# patience (bench_autotune's full preset) pass a wider grid.
DEFAULT_CHUNK_CANDIDATES = (16, 64, 256)
DEFAULT_BLOCK_C_CANDIDATES = (128, 256)


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """One tuning outcome: the winning constants plus the evidence."""

    chunk_steps: int
    block_c: int
    execution: str
    steps_per_s: float
    # the incumbent (hand-chosen) config measured under the identical
    # protocol — the bench gate reports tuned vs this
    baseline_steps_per_s: float
    source: str  # "measured" | "cache"
    # ((chunk_steps, block_c, execution, steps_per_s), ...) for the report
    candidates: tuple = ()


def default_cache_path() -> str:
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "autotune.json"
    )


def tune_key(config: EngineConfig, target, init_words) -> str:
    """The cache identity: what the measurement depends on — workload
    kind + state layout + engine axes + device — and nothing it doesn't
    (the tuned knobs themselves, seeds, step counts)."""
    devices = jax.devices()
    words = jax.numpy.asarray(init_words)
    parts = (
        config.update,
        config.randomness,
        type(target).__name__,
        "x".join(str(int(s)) for s in words.shape) or "scalar",
        str(words.dtype),
        f"C{config.num_chains}",
        config.collect,
        jax.default_backend(),
        devices[0].device_kind.replace(" ", "_"),
        f"D{len(devices)}",
    )
    return "|".join(parts)


def _load_cache(path: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    return data if isinstance(data, dict) else {}


def _store_cache(path: str, cache: dict) -> None:
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    os.replace(tmp, path)  # atomic: readers never see a torn file


def _eligible_executions(config: EngineConfig, target) -> list[str]:
    """Concrete backends worth measuring: always scan, plus pallas when
    the target/rule can fuse.  An explicit config.execution pin narrows
    the grid to that backend (the user already chose)."""
    if config.execution in ("scan", "pallas"):
        return [config.execution]
    out = ["scan"]
    try:
        resolve_execution("pallas", target, config.update)
        out.append("pallas")
    except IneligibleExecution:
        pass
    return out


def measure_config(
    config: EngineConfig, target, init_words, *, key=None,
    n_steps: int = 256, repeats: int = 3,
) -> float:
    """Best-of-N steps/s of one candidate config — the bench harness
    protocol (warm-up pays the compile; the minimum tracks compute on a
    loaded machine).  Raises ``IneligibleExecution`` on a candidate the
    engine refuses (shape/backend) — callers filter."""
    engine = MHEngine(config)
    plan = RunPlan(
        target=target,
        n_steps=n_steps,
        init_words=init_words,
        key=key if key is not None else jax.random.PRNGKey(0),
    )
    jax.block_until_ready(
        engine.submit(plan, compiled=True).result.final_words
    )
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        r = engine.submit(plan, compiled=True).result
        jax.block_until_ready(r.final_words)
        best = min(best, time.perf_counter() - t0)
    size = max(1, int(jax.numpy.asarray(init_words).size))
    return n_steps * size / max(best, 1e-9)


def autotune_config(
    config: EngineConfig,
    target,
    init_words,
    *,
    key=None,
    n_steps: int = 256,
    repeats: int = 3,
    chunk_candidates=DEFAULT_CHUNK_CANDIDATES,
    block_c_candidates=DEFAULT_BLOCK_C_CANDIDATES,
    cache_path: str | None = None,
    refresh: bool = False,
) -> tuple[EngineConfig, TuneResult]:
    """Tuned ``(config, evidence)`` for this (workload, shape, device).

    Cache hit: returns the stored winner without measuring.  Miss (or
    ``refresh=True``): measures the candidate grid — incumbent first, so
    the argmax can never lose to it — stores, and returns.  Candidates
    the engine rejects (``IneligibleExecution``: pallas on an unfusable
    target/shape) are dropped; the incumbent itself failing is an
    error, and so is any other failure — a kernel the compiler refuses
    must not quietly lose to scan.
    """
    path = cache_path if cache_path is not None else default_cache_path()
    ckey = tune_key(config, target, init_words)
    cache = _load_cache(path)
    hit = cache.get(ckey)
    if hit and not refresh and hit.get("version") == CACHE_VERSION:
        tuned = dataclasses.replace(
            config,
            chunk_steps=int(hit["chunk_steps"]),
            block_c=int(hit["block_c"]),
            execution=str(hit["execution"]),
        )
        return tuned, TuneResult(
            chunk_steps=int(hit["chunk_steps"]),
            block_c=int(hit["block_c"]),
            execution=str(hit["execution"]),
            steps_per_s=float(hit["steps_per_s"]),
            baseline_steps_per_s=float(hit["baseline_steps_per_s"]),
            source="cache",
            candidates=tuple(
                tuple(c) for c in hit.get("candidates", ())
            ),
        )

    executions = _eligible_executions(config, target)
    incumbent_exec = (
        config.execution
        if config.execution in ("scan", "pallas")
        else resolve_execution(config.execution, target, config.update)
    )
    grid: list[tuple[int, int, str]] = [
        (config.chunk_steps, config.block_c, incumbent_exec)
    ]
    for execution in executions:
        blocks = (
            block_c_candidates
            if (execution == "pallas" and config.update == "mh")
            else (config.block_c,)
        )
        for chunk in chunk_candidates:
            for block_c in blocks:
                cand = (int(chunk), int(block_c), execution)
                if cand not in grid:
                    grid.append(cand)

    measured: list[tuple[int, int, str, float]] = []
    for i, (chunk, block_c, execution) in enumerate(grid):
        cand_cfg = dataclasses.replace(
            config, chunk_steps=chunk, block_c=block_c, execution=execution
        )
        with telemetry.span(
            "autotune.measure",
            chunk_steps=chunk, block_c=block_c, execution=execution,
            incumbent=(i == 0),
        ) as sp:
            try:
                rate = measure_config(
                    cand_cfg, target, init_words, key=key, n_steps=n_steps,
                    repeats=repeats,
                )
            except IneligibleExecution:
                sp.set(outcome="ineligible")
                if i == 0:  # the incumbent must run — no fallback
                    raise
                continue
            sp.set(outcome="ok", steps_per_s=round(rate, 1))
        measured.append((chunk, block_c, execution, rate))

    baseline_rate = measured[0][3]
    chunk, block_c, execution, rate = max(measured, key=lambda m: m[3])
    telemetry.log(
        "autotune.result",
        chunk_steps=chunk, block_c=block_c, execution=execution,
        steps_per_s=round(rate, 1),
        baseline_steps_per_s=round(baseline_rate, 1),
        candidates=len(measured),
    )
    result = TuneResult(
        chunk_steps=chunk,
        block_c=block_c,
        execution=execution,
        steps_per_s=rate,
        baseline_steps_per_s=baseline_rate,
        source="measured",
        candidates=tuple(measured),
    )
    cache[ckey] = {
        "version": CACHE_VERSION,
        "chunk_steps": chunk,
        "block_c": block_c,
        "execution": execution,
        "steps_per_s": rate,
        "baseline_steps_per_s": baseline_rate,
        "candidates": [list(m) for m in measured],
    }
    _store_cache(path, cache)
    tuned = dataclasses.replace(
        config, chunk_steps=chunk, block_c=block_c, execution=execution
    )
    return tuned, result


def autotune_engine(
    engine: MHEngine, target, init_words, **kwargs
) -> tuple[MHEngine, TuneResult]:
    """``autotune_config`` for an existing engine: returns a fresh engine
    on the tuned config (engines are cheap; the jit caches key on engine
    identity, so a new instance also keeps tuned traces separate)."""
    tuned_cfg, result = autotune_config(
        engine.config, target, init_words, **kwargs
    )
    return MHEngine(tuned_cfg), result
