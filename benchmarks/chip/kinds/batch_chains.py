"""Batch traffic over independent chains sharded across the cell's chips.

As ``batch.py``, with the engine's chains axis (DESIGN.md §Chains-axis)
over a mesh of the cell's devices: the workload is built once with the
traffic's ``builder`` sizes (``num_chains`` chains of ``batch``
lattices), its initial state is placed one chain a chip, and each job
is ``engine.submit(wl.plan(key, mesh=make_chains_mesh(C,
devices=r.devices)), compiled=...)``, ending in ``block_until_ready``.
Chain c of a job is by contract the one-chain job of chain id c.

``gibbs_updates_per_s`` counts the updates of every chain.  The job's
Threefry blocks and bytes for the roofline are one chain's: each chip
runs one chain, and the trace's busy time is the mean over the chips.

Set-up runs one warm-up job (a job takes seconds on each chip, and the
first compiles all the window runs) on a key of its own.

The reference checks every chain of one job drawn from the seed among
the first two and of the window's last job against the configuration's
one-chain reference (``_sweeps``): chain c starts from
``bernoulli(fold_in(build key, c))`` and streams from the key words of
``fold_in(run key, c)``.  The chains' references run on their own chips
at once.
"""

from __future__ import annotations

import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import lib
import stream

_batch = lib.load_module(Path(__file__).with_name("batch.py"))
WARMUP_JOBS = 1
job_spec = _batch.job_spec
job_case = _batch.job_case
control_cases = _batch.control_cases
release = _batch.release


def _chains(r) -> int:
    return int(r.traffic["builder"]["num_chains"])


def setup(r):
    from repro import workloads
    from repro.launch.mesh import make_chains_mesh

    tr = r.traffic
    base = stream.key_from_seed(r.seed)
    wl = workloads.build(
        r.cfg["workload"], jax.random.fold_in(base, 0),
        randomness=tr["randomness"], backend=tr["execution"],
        chunk_steps=int(tr["chunk_steps"]), collect=tr["collect"],
        n_steps=int(tr["n_steps"]), **r.cfgmod.builder_kwargs(r.cfg),
        **tr["builder"],
    )
    mesh = make_chains_mesh(_chains(r), devices=r.devices)
    wl.init_words = jax.device_put(
        wl.init_words, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(mesh.axis_names[0])))
    idx = jnp.arange(_batch.MAX_JOBS, dtype=jnp.int32)
    job_keys = jax.vmap(
        lambda j: jax.random.fold_in(jax.random.fold_in(base, 1), j))(idx)
    warm_keys = jax.vmap(
        lambda j: jax.random.fold_in(jax.random.fold_in(base, 2), j))(
            jnp.arange(WARMUP_JOBS, dtype=jnp.int32))
    state = _batch.State(wl=wl, job_keys=job_keys, job=job_spec(r))
    state.mesh = mesh
    for j in range(WARMUP_JOBS):
        jax.block_until_ready(_outputs(_submit(r, state, warm_keys[j])))
    return state


def _submit(r, state, key):
    return state.wl.engine.submit(
        state.wl.plan(key, mesh=state.mesh),
        compiled=bool(r.traffic["compiled"]),
    ).result


def _outputs(result) -> dict:
    return {k: getattr(result, k) for k in ("final_words", "accept_count")}


def window(r, state):
    pick = int(lib.seed_rng(r.seed, 1).integers(0, 2))
    jobs, kept, last, traced = [], {}, None, None
    r.traced.begin()
    t0 = time.perf_counter()
    while True:
        j = len(jobs)
        if j == _batch.MAX_JOBS:
            raise RuntimeError(f"more than {_batch.MAX_JOBS} jobs in the window")
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
    per_job = mod.job_updates(cfg, state.job) * _chains(r)
    rate = len(jobs) * per_job / (jobs[-1]["t_done"] - t0)
    return _batch.Result(
        attempted=len(jobs), failed=0,
        e2e={f"{cfg['update']}_updates_per_s": rate},
        jobs=jobs, traced_jobs=traced or len(jobs), kept=kept,
        job_blocks=mod.job_blocks(cfg, state.job, r.traffic["randomness"]),
        job_bytes=mod.job_bytes(cfg, state.job),
    )


def check(r, res) -> dict:
    """Numbers compared, summed over every chain of the checked jobs."""
    total = {"sites_off": 0, "flips_off": 0}
    for j, out in sorted(res.kept.items()):
        case = job_case(r, j)
        refs = chain_references(r, case)
        final = np.asarray(out["final_words"])
        count = np.asarray(out["accept_count"])
        for c, ref in enumerate(refs):
            total["sites_off"] += int(np.count_nonzero(
                final[c] != ref["final_words"]))
            total["flips_off"] += int(np.count_nonzero(
                count[c] != ref["accept_count"]))
    res.kept.clear()
    return total


def chain_references(r, case: dict, precision: str = "float32") -> list:
    """Final words and flip counts of every chain of one job, by the
    configuration's one-chain reference, chain c on device c % devices."""
    cfg, mod = r.cfg, r.cfgmod
    batch, n = int(case["batch"]), int(case["n_steps"])
    h, w = cfg["height"], cfg["width"]
    per = h * w
    block = max(1, mod.BLOCK_SITES // per)
    devices = getattr(r, "devices", None) or jax.devices()[:1]
    pending = []
    for c in range(int(case["num_chains"])):
        dev = devices[c % len(devices)]
        init = jax.random.bernoulli(
            jax.random.fold_in(case["build_key"], c), 0.5, (batch, h, w)
        ).astype(jnp.uint32)
        k0, k1 = stream.chain_words(case["run_key"], c)
        parts = []
        for b in range(0, batch, block):
            nb = min(block, batch - b)
            site = (jnp.arange(nb * per, dtype=jnp.uint32)
                    + jnp.uint32(b * per)).reshape(nb, h, w)
            args = jax.device_put(
                (init[b:b + nb], site, k0, k1, jnp.float32(cfg["beta"]),
                 jnp.float32(cfg["field"])), dev)
            parts.append(mod._sweeps(
                args[0], args[1], args[2], args[3], n, args[4], args[5],
                precision=precision, u_salt=cfg["stream"]["u_salt"]))
        pending.append(parts)
    return [
        {"final_words": np.concatenate([np.asarray(f) for f, _ in parts]),
         "accept_count": np.concatenate([np.asarray(a) for _, a in parts])}
        for parts in pending
    ]
