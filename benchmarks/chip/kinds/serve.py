"""Serve traffic: open-loop requests through the program's serving tier.

The window calls ``Scheduler.serve(requests, realtime=True)`` as
``launch/serve_engine.py`` does: the scheduler admits each request once
its due time has passed, packs it into a free slot, and stamps its
result when the host has it.  The traffic file names the slots, the
lattices (or chains) per request (``builder``), the randomness, the
execution, the chunk, the arrival rate, and the range of step budgets.

Arrivals are a Poisson process at ``rate`` per second over the window;
step budgets are log-uniform over [``steps_lo``, ``steps_hi``], rounded
to the chunk so that every segment is the warmed program.  Both are
fixed quantiles in an order drawn once (``lib``, stream ``SCHEDULE``),
not from the run's seed: which long requests overlap moves the tail by
some 10% from one order to another, far more than two runs of one
order differ, so every seed offers the same work at the same times.
The run's seed draws each request's own seed, its initial lattices and
its randomness stream.

Set-up warms the same ``Scheduler`` instance (a new one recompiles) with
two bursts that fill every slot.  Latency runs from a request's due
time to its result on the host; one that never finishes counts as
infinitely late.  The reference checks a sample of the requests, drawn
from the seed, with the longest among them.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import numpy as np

import lib

CHECKED = 16
SCHEDULE = 5    # the stream of the one fixed order (seed 0)


@dataclasses.dataclass
class State:
    sched: object
    requests: list


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    e2e: dict
    requests: list
    t_begin: float                  # scheduler clock at the window start
    t_end: float                    # scheduler clock when the last result landed
    wall_s: float                   # host seconds inside Scheduler.serve
    slots: int


def _request(workload, rid, seed, n_steps, t_arrive, collect):
    from repro.serving import ServeRequest

    return ServeRequest(rid=rid, workload=workload, n_steps=int(n_steps),
                        seed=int(seed), collect=collect,
                        t_arrive=float(t_arrive))


def make_requests(r, rng) -> list:
    """The window's requests, due times relative to the window start;
    ``rng`` (from the run's seed) draws the requests' seeds."""
    tr = r.traffic
    n = int(round(float(tr["rate"]) * r.seconds))
    order = lib.seed_rng(0, SCHEDULE)
    arrivals = np.cumsum(lib.poisson_gaps(n, float(tr["rate"]), order))
    steps = lib.log_uniform_steps(n, int(tr["steps_lo"]), int(tr["steps_hi"]),
                                  int(tr["chunk_steps"]), order)
    seeds = rng.integers(0, 2**31 - 1, n)
    return [
        _request(r.cfg["workload"], i, seeds[i], steps[i], arrivals[i],
                 tr["collect"])
        for i in range(n)
    ]


def setup(r) -> State:
    from repro.serving import Scheduler

    tr = r.traffic
    sched = Scheduler(
        n_slots=int(tr["slots"]), randomness=tr["randomness"],
        execution=tr["execution"], smoke=False,
        chunk_steps=int(tr["chunk_steps"]),
        workload_kwargs={**r.cfgmod.builder_kwargs(r.cfg),
                         **tr.get("builder", {})},
    )
    rng = lib.seed_rng(r.seed, 2)
    requests = make_requests(r, rng)
    name = r.cfg["workload"]
    warm = rng.integers(0, 2**31 - 1, 2 * sched.n_slots)
    for burst in (warm[:sched.n_slots], warm[sched.n_slots:]):
        sched.serve([_request(name, -1 - i, s, 2 * int(tr["chunk_steps"]),
                              0.0, tr["collect"])
                     for i, s in enumerate(burst)])
    return State(sched=sched, requests=requests)


def window(r, state: State) -> Result:
    sched, reqs = state.sched, state.requests
    before = sched.compiled_programs
    n_done = len(sched.done)
    begin = sched.clock()
    for q in reqs:
        q.t_arrive += begin
    r.traced.begin()
    w0 = time.perf_counter()
    with r.annotate("serve"):
        sched.serve(reqs, realtime=True)
    wall = time.perf_counter() - w0
    r.traced.end()
    r.log(f"class programs compiled inside the window: "
          f"{sched.compiled_programs - before}")
    finished = {id(q) for q in sched.done[n_done:]}
    lat = [q.latency_s if id(q) in finished and q.t_done is not None
           else math.inf for q in reqs]
    failed = sum(1 for x in lat if x == math.inf)
    end = max((q.t_done for q in reqs if q.t_done is not None),
              default=begin)
    return Result(
        attempted=len(reqs), failed=failed,
        e2e={"p95_latency_s": lib.percentile(lat, 95),
             "p50_latency_s": lib.percentile(lat, 50)},
        requests=reqs, t_begin=begin, t_end=end, wall_s=wall,
        slots=sched.n_slots,
    )


def release(state: State) -> None:
    state.sched = None


def check(r, res: Result) -> dict:
    """Numbers compared, summed over the checked requests; a request
    that never finished makes every number infinite."""
    done = [q for q in res.requests if q.final_words is not None]
    if len(done) < len(res.requests):
        return {"unfinished": len(res.requests) - len(done)}
    total = {"unfinished": 0}
    for q in checked(r, done):
        out = {"final_words": q.final_words,
               "accept_count": q.accept_count, "samples": q.samples}
        for key, val in r.cfgmod.compare(r.cfg, case_of(r, q), out).items():
            total[key] = total.get(key, 0) + val
    return total


def checked(r, requests: list) -> list:
    """The requests the reference redoes: the longest, and others drawn
    from the seed."""
    rng = lib.seed_rng(r.seed, 3)
    longest = max(range(len(requests)), key=lambda i: requests[i].n_steps)
    others = [i for i in range(len(requests)) if i != longest]
    picks = [longest] + list(rng.choice(
        others, size=min(CHECKED - 1, len(others)), replace=False))
    return [requests[int(i)] for i in picks]


def case_of(r, q) -> dict:
    """What the reference needs to redo request ``q``: the serving
    tier's per-request keys (split of PRNGKey(seed) into the build key
    and the run key) and sizes."""
    k_init, k_run = jax.random.split(jax.random.PRNGKey(q.seed))
    return dict(build_key=k_init, run_key=k_run, n_steps=q.n_steps,
                batch=int(r.traffic.get("builder", {}).get("batch", 1)),
                randomness=r.traffic["randomness"], collect=q.collect)


def control_cases(r) -> list:
    """The cases a run on seed ``r.seed`` checks, without running it."""
    return [case_of(r, q)
            for q in checked(r, make_requests(r, lib.seed_rng(r.seed, 2)))]
