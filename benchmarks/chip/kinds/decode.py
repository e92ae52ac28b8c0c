"""Decode traffic: back-to-back decode steps of token sampling.

Each decode step is one job: ``engine.sample_tokens(key, logits,
n_steps, temperature)``, the entry ``launch/serve.py``'s decode reaches,
over one (batch, vocab) logits table, and every job ends in
``block_until_ready``.  The workload is built once
(``workloads.build(cfg["workload"], ...)`` with the traffic's
randomness, execution, chunk and collection mode); its module draws the
configuration's ``tables`` logits tables on the device at set-up, table
j from ``fold_in(build key, j)``, and job j samples table ``j mod
tables``, so no job reuses the previous job's table.

Job j streams from ``fold_in(fold_in(seed key, 1), j)``; the keys are
drawn at set-up and held on the host, so a job dispatches the decode
step's program alone (an eager slice of a key array on the device would
be a program of its own, with its own dispatch).  Set-up runs
``WARMUP_JOBS`` jobs on keys of their own, so the window compiles
nothing.  The window runs jobs until ``--seconds`` have passed;
``mh_updates_per_s`` is the chain-steps of every job over the time from
the first call to the last job's end.  A traced run's profiler covers
the whole jobs of the window's first ``trace_seconds``, and no more
than ``TRACE_JOBS`` of them: a decode step takes about a millisecond,
and the trace's idle gaps are labelled gap by span.  Telemetry stops
with the profiler, so the program's spans cover the same jobs.

The reference checks one job drawn from the seed among the first two
and the window's last job.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

import lib
import stream

MAX_JOBS = 1 << 18
WARMUP_JOBS = 2
TRACE_JOBS = 200


@dataclasses.dataclass
class State:
    wl: object
    tables: list
    job_keys: np.ndarray             # (MAX_JOBS, 2) uint32, on the host
    job: dict


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    e2e: dict
    jobs: list                       # per job: t_submit, t_return, t_done
    traced_jobs: int                 # jobs inside the profiler's window
    kept: dict                       # job index -> outputs on the device
    job_blocks: int | None
    job_bytes: int
    kernel_blocks: int
    kernel_bytes: int


def job_spec(r) -> dict:
    tr = r.traffic
    return dict(n_steps=int(tr["n_steps"]), collect=tr["collect"],
                randomness=tr["randomness"])


def setup(r) -> State:
    from repro import workloads
    from repro.workloads import tokens

    tr, cfg = r.traffic, r.cfg
    base = stream.key_from_seed(r.seed)
    build_key = jax.random.fold_in(base, 0)
    wl = workloads.build(
        cfg["workload"], build_key, randomness=tr["randomness"],
        backend=tr["execution"], chunk_steps=int(tr["chunk_steps"]),
        collect=tr["collect"], n_steps=int(tr["n_steps"]),
        **r.cfgmod.builder_kwargs(cfg),
    )
    tables = [
        jax.block_until_ready(tokens.table_logits(
            build_key, j, int(cfg["batch"]), int(cfg["vocab"])))
        for j in range(int(cfg["tables"]))
    ]
    idx = jnp.arange(MAX_JOBS, dtype=jnp.int32)
    job_keys = np.asarray(jax.vmap(
        lambda j: jax.random.fold_in(jax.random.fold_in(base, 1), j))(idx))
    warm_keys = np.asarray(jax.vmap(
        lambda j: jax.random.fold_in(jax.random.fold_in(base, 2), j))(
            jnp.arange(WARMUP_JOBS, dtype=jnp.int32)))
    state = State(wl=wl, tables=tables, job_keys=job_keys, job=job_spec(r))
    for j in range(WARMUP_JOBS):
        jax.block_until_ready(_sample(r, state, warm_keys[j], j))
    return state


def _sample(r, state: State, key, j: int) -> dict:
    tokens, result = state.wl.engine.sample_tokens(
        key, state.tables[j % len(state.tables)],
        n_steps=int(r.traffic["n_steps"]),
        temperature=float(r.cfg["temperature"]),
    )
    return {"tokens": tokens, "final_words": result.final_words,
            "accept_count": result.accept_count,
            "final_logp": result.final_logp}


def window(r, state: State) -> Result:
    from repro import telemetry

    pick = int(lib.seed_rng(r.seed, 1).integers(0, 2))
    jobs, kept, last, traced = [], {}, None, None
    r.traced.begin()
    t0 = time.perf_counter()
    while True:
        j = len(jobs)
        if j == MAX_JOBS:
            raise RuntimeError(f"more than {MAX_JOBS} jobs in the window")
        key = state.job_keys[j]
        with r.annotate("submit"):
            ts = time.perf_counter()
            result = _sample(r, state, key, j)
            tr = time.perf_counter()
        with r.annotate("block"):
            out = jax.block_until_ready(result)
        td = time.perf_counter()
        jobs.append({"t_submit": ts, "t_return": tr, "t_done": td})
        if j == pick:
            kept[j] = out
        last = (j, out)
        if r.traced.open and (td - t0 >= r.trace_seconds
                              or j + 1 >= TRACE_JOBS):
            r.traced.end()
            telemetry.disable()
            traced = j + 1
        if td - t0 >= r.seconds:
            break
    r.traced.end()
    kept[last[0]] = last[1]
    cfg, mod = r.cfg, r.cfgmod
    per_job = mod.job_updates(cfg, state.job)
    rate = len(jobs) * per_job / (jobs[-1]["t_done"] - t0)
    return Result(
        attempted=len(jobs), failed=0,
        e2e={f"{cfg['update']}_updates_per_s": rate},
        jobs=jobs, traced_jobs=traced or len(jobs), kept=kept,
        job_blocks=mod.job_blocks(cfg, state.job, r.traffic["randomness"]),
        job_bytes=mod.job_bytes(cfg, state.job),
        kernel_blocks=mod.kernel_blocks(cfg, state.job),
        kernel_bytes=mod.kernel_bytes(cfg, state.job),
    )


def release(state: State) -> None:
    state.wl = None
    state.tables = None


def check(r, res: Result) -> dict:
    """Numbers compared, summed over the checked jobs."""
    total: dict = {}
    for j, out in sorted(res.kept.items()):
        for key, val in r.cfgmod.compare(r.cfg, job_case(r, j), out).items():
            total[key] = total.get(key, 0) + val
    res.kept.clear()
    return total


def job_case(r, j: int) -> dict:
    """What the reference needs to redo job ``j``: its keys, its table
    and its steps."""
    base = stream.key_from_seed(r.seed)
    return dict(
        build_key=jax.random.fold_in(base, 0),
        run_key=jax.random.fold_in(jax.random.fold_in(base, 1), j),
        table=j % int(r.cfg["tables"]),
        **job_spec(r),
    )


def control_cases(r) -> list:
    """Cases like those a run on seed ``r.seed`` checks, without running
    it: the first two jobs."""
    return [job_case(r, j) for j in (0, 1)]
