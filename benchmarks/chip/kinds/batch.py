"""Batch traffic: back-to-back sampling jobs, one at a time.

Each job goes through the program's batch entry, as
``launch/sample.py`` drives it: ``workloads.build(...)`` once, then
``engine.submit(wl.plan(key))`` per job, and every job ends in
``block_until_ready``.  The traffic file names the randomness, the
execution, the collection mode, the chunk, the steps per job, any
builder sizes (``builder``), and whether jobs go through the engine's
cached one-dispatch entry (``compiled``: ``submit(plan,
compiled=True)``) or the direct one.

Job j streams from ``fold_in(fold_in(seed key, 1), j)``; every job
starts from the initial state built from ``fold_in(seed key, 0)``.
Set-up runs ``WARMUP_JOBS`` jobs on keys of their own, so the window
compiles nothing.  The window runs jobs until ``--seconds`` have
passed; ``<update>_updates_per_s`` (``mh`` or ``gibbs``, the
configuration's update rule) is the updates of every job over the time
from the first submit to the last job's end.  A traced run's profiler
covers the whole jobs of the window's first ``trace_seconds``.

The reference checks one job drawn from the seed among the first two
and the window's last job.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

import lib
import stream

MAX_JOBS = 4096
WARMUP_JOBS = 2
OUTPUTS = ("samples", "final_words", "accept_count")


@dataclasses.dataclass
class State:
    wl: object
    job_keys: object
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


def job_spec(r) -> dict:
    tr = r.traffic
    return dict(n_steps=int(tr["n_steps"]), collect=tr["collect"],
                randomness=tr["randomness"], **tr.get("builder", {}))


def setup(r) -> State:
    from repro import workloads

    tr = r.traffic
    base = stream.key_from_seed(r.seed)
    build_key = jax.random.fold_in(base, 0)
    wl = workloads.build(
        r.cfg["workload"], build_key, randomness=tr["randomness"],
        backend=tr["execution"], chunk_steps=int(tr["chunk_steps"]),
        collect=tr["collect"], n_steps=int(tr["n_steps"]),
        **r.cfgmod.builder_kwargs(r.cfg), **tr.get("builder", {}),
    )
    idx = jnp.arange(MAX_JOBS, dtype=jnp.int32)
    job_keys = jax.vmap(
        lambda j: jax.random.fold_in(jax.random.fold_in(base, 1), j))(idx)
    warm_keys = jax.vmap(
        lambda j: jax.random.fold_in(jax.random.fold_in(base, 2), j))(idx)
    state = State(wl=wl, job_keys=job_keys, job=job_spec(r))
    for j in range(WARMUP_JOBS):
        jax.block_until_ready(_outputs(_submit(r, state, warm_keys[j])))
    return state


def _submit(r, state: State, key):
    return state.wl.engine.submit(
        state.wl.plan(key), compiled=bool(r.traffic["compiled"])
    ).result


def _outputs(result) -> dict:
    return {k: getattr(result, k) for k in OUTPUTS}


def window(r, state: State) -> Result:
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
            result = _submit(r, state, key)
            tr = time.perf_counter()
        with r.annotate("block"):
            out = jax.block_until_ready(_outputs(result))
        td = time.perf_counter()
        jobs.append({"t_submit": ts, "t_return": tr, "t_done": td})
        if j == pick:
            kept[j] = out
        last = (j, out)
        if r.traced.open and td - t0 >= r.trace_seconds:
            r.traced.end()
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
    )


def release(state: State) -> None:
    state.wl = None


def check(r, res: Result) -> dict:
    """Numbers compared, summed over the checked jobs."""
    total: dict = {}
    for j, out in sorted(res.kept.items()):
        case = job_case(r, j)
        for key, val in r.cfgmod.compare(r.cfg, case, out).items():
            total[key] = total.get(key, 0) + val
    res.kept.clear()
    return total


def job_case(r, j: int) -> dict:
    """What the reference needs to redo job ``j``: its keys and sizes."""
    base = stream.key_from_seed(r.seed)
    return dict(
        build_key=jax.random.fold_in(base, 0),
        run_key=jax.random.fold_in(jax.random.fold_in(base, 1), j),
        **job_spec(r),
    )


def control_cases(r) -> list:
    """Cases like those a run on seed ``r.seed`` checks, without running
    it: the first two jobs."""
    return [job_case(r, j) for j in (0, 1)]
