"""Smoke run of the sampler engine and serving tier on a TPU.

    python chip_smoke.py               # one chip: batch runs, parity, serving
    python chip_smoke.py --four-chips  # four chips: sharded vs one device

One chip drives the main path (``RunPlan`` -> ``MHEngine.submit`` ->
``serving.Scheduler``) at deployment sizes, through the same calls
``launch/sample.py`` and ``launch/serve_engine.py`` make:

  * MH batch: gmm (V=256 grid table), 4096 compartment chains, 2048
    steps, randomness {fused, cim} x execution {pallas, scan}; the kept
    samples after burn-in are checked against the exact distribution
    ``softmax(table)`` by TV distance.
  * Gibbs batch: ising, 64 lattices of 128x128 at beta=0.35, 1024
    half-sweeps in 32-step chunks, ``collect="last"``, same four cells;
    the mean nearest-neighbour correlation is checked against Onsager's
    exact value.
  * Executor parity: final-state words that differ between pallas and
    scan in each cell and over the served burst; any difference fails.
  * Serving: a 16-slot ``Scheduler`` takes a mixed ising+gmm burst of
    32 requests under pallas and under scan; three requests must equal
    their solo ``engine.submit`` runs bit for bit.
  * Device check: every pallas program's lowered text holds a
    ``tpu_custom_call``, i.e. the kernel ran compiled, not interpreted.

``--four-chips`` runs only the sharded path: a chains-mesh ising run
(pallas and scan) and a mesh-sharded scan serving burst, each compared
bit for bit with the same work on one device.

The script refuses to run anywhere but a TPU.  Timings it prints are
wall-clock on the named device, compile included where marked.  The last
line of stdout is one JSON object: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BETA = 0.35  # the ising workload's coupling: high-temperature phase

# One-chip sizes: the deployment sizes the smoke runs at.
GMM_CHAINS = 4096
GMM_STEPS = 2048
LATTICES = 64
SIDE = 128
ISING_STEPS = 1024
CHUNK_STEPS = 32
SLOTS = 16
REQUESTS = 32
# --four-chips
N_DEVICES = 4
MESH_CHAINS = 8
MESH_LATTICES = 8
MESH_STEPS = 256


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_label() -> str:
    import jax

    d = jax.devices()[0]
    return f"{d.platform}:{d.device_kind}"


def has_kernel(fn, *args) -> bool:
    """Whether ``jit(fn)`` lowers to a compiled Pallas TPU kernel."""
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def submit_fn(engine, target, n_steps, collect):
    """The traceable ``engine.submit`` call the device check lowers."""
    from repro.samplers import RunPlan

    def run(key, words):
        plan = RunPlan(
            target=target, n_steps=n_steps, init_words=words, key=key,
            collect=collect,
        )
        return engine.submit(plan).result.final_words

    return run


# --- references -------------------------------------------------------------


def onsager_nn_correlation(beta: float) -> float:
    """Exact <s_i s_j> for nearest neighbours of the infinite 2-D Ising
    model at zero field (Onsager): minus the energy per bond,
    (1/2) coth(2b) [1 + (2/pi)(2 tanh^2(2b) - 1) K(k)],
    k = 2 sinh(2b) / cosh^2(2b), K the complete elliptic integral of
    the first kind, here by the arithmetic-geometric mean."""
    t = 2.0 * beta
    k = 2.0 * math.sinh(t) / math.cosh(t) ** 2
    a, b = 1.0, math.sqrt(1.0 - k * k)
    while abs(a - b) > 1e-15:
        a, b = (a + b) / 2.0, math.sqrt(a * b)
    big_k = math.pi / (2.0 * a)
    return 0.5 / math.tanh(t) * (
        1.0 + (2.0 / math.pi) * (2.0 * math.tanh(t) ** 2 - 1.0) * big_k
    )


def bond_correlations(words):
    """Mean nearest-neighbour s_i s_j per lattice of (B, H, W) words."""
    import numpy as np

    s = 2.0 * np.asarray(words, np.float64) - 1.0
    bonds = s * np.roll(s, -1, -1) + s * np.roll(s, -1, -2)
    return bonds.mean(axis=(-2, -1)) / 2.0


def tv_check(samples, table, burn_in: int):
    """(TV distance, bound) of the kept MH samples after burn-in against
    softmax(table).  ``samples`` is (T, 1, C): C independent chains.
    Each chain's visit frequency per bin is one draw; the standard error
    of a bin's pooled frequency is the spread of those draws across
    chains over sqrt(C), which takes in the chains' autocorrelation.
    With no bias E[TV] is about 0.4 x the sum of those errors; the bound
    lets every bin sit 2 standard errors out (1 x the sum), 2.5 times
    the expected TV, and well under the TV of one misread bin."""
    import numpy as np

    logits = np.asarray(table, np.float64)[0]
    v = logits.size
    p = np.exp(logits - logits.max())
    p /= p.sum()
    kept = np.asarray(samples)[burn_in:]
    kept = kept.reshape(kept.shape[0], -1).astype(np.int64)  # (T', C)
    steps, chains = kept.shape
    per_chain = np.bincount(
        (kept + v * np.arange(chains)).reshape(-1), minlength=chains * v
    ).reshape(chains, v) / steps
    freq = per_chain.mean(axis=0)
    se = per_chain.std(axis=0, ddof=1) / math.sqrt(chains)
    tv = 0.5 * float(np.abs(freq - p).sum())
    bound = 0.5 * float((2.0 * se).sum())
    return tv, bound


# --- one chip ---------------------------------------------------------------


def run_batch() -> dict:
    """MH and Gibbs batch cells; returns final words per cell."""
    import jax
    import numpy as np

    from repro import workloads

    dev = device_label()
    finals = {}
    exact = onsager_nn_correlation(BETA)
    for randomness in ("fused", "cim"):
        for execution in ("pallas", "scan"):
            cell = f"gmm/{randomness}/{execution}"
            wl = workloads.build(
                "gmm", jax.random.PRNGKey(0), randomness=randomness,
                backend=execution, chains=GMM_CHAINS,
                n_steps=GMM_STEPS, chunk_steps=CHUNK_STEPS,
            )
            key = jax.random.PRNGKey(1)
            if execution == "pallas":
                check(
                    has_kernel(
                        submit_fn(wl.engine, wl.target, wl.n_steps, "all"),
                        key, wl.init_words,
                    ),
                    f"{cell}: no tpu_custom_call in the lowered program",
                )
            t0 = time.perf_counter()
            res = wl.engine.submit(wl.plan(key)).result
            jax.block_until_ready(res.samples)
            dt = time.perf_counter() - t0
            tv, bound = tv_check(res.samples, wl.target.table, wl.burn_in)
            rate = float(res.acceptance_rate)
            log(
                f"{cell}: tv={tv:.6f} bound={bound:.6f} "
                f"acceptance_rate={rate:.4f} "
                f"chain_steps/s={GMM_STEPS * GMM_CHAINS / dt:.4g} "
                f"(wall {dt:.3f} s incl. compile, {dev})"
            )
            check(np.isfinite(rate) and 0.0 < rate < 1.0,
                  f"{cell}: acceptance rate {rate}")
            check(tv <= bound, f"{cell}: TV {tv} exceeds bound {bound}")
            finals[cell] = np.asarray(res.final_words)

            cell = f"ising/{randomness}/{execution}"
            wl = workloads.build(
                "ising", jax.random.PRNGKey(2), randomness=randomness,
                backend=execution, height=SIDE, width=SIDE,
                batch=LATTICES, n_steps=ISING_STEPS,
                chunk_steps=CHUNK_STEPS, collect="last", beta=BETA,
            )
            key = jax.random.PRNGKey(3)
            if execution == "pallas":
                check(
                    has_kernel(
                        submit_fn(wl.engine, wl.target, wl.n_steps, "last"),
                        key, wl.init_words,
                    ),
                    f"{cell}: no tpu_custom_call in the lowered program",
                )
            t0 = time.perf_counter()
            res = wl.engine.submit(wl.plan(key)).result
            jax.block_until_ready(res.final_words)
            dt = time.perf_counter() - t0
            corr = bond_correlations(res.final_words)
            mean = float(corr.mean())
            se = float(corr.std(ddof=1) / math.sqrt(corr.size))
            sites = LATTICES * SIDE * SIDE
            log(
                f"{cell}: nn_corr={mean:.6f} onsager={exact:.6f} "
                f"|diff|={abs(mean - exact):.6f} bound(5se)={5 * se:.6f} "
                f"flip_rate={float(res.acceptance_rate):.4f} "
                f"site_updates/s={sites * ISING_STEPS / dt:.4g} "
                f"(wall {dt:.3f} s incl. compile, {dev})"
            )
            check(res.samples.shape[0] == 0, f"{cell}: collect=last kept rows")
            check(abs(mean - exact) <= 5 * se,
                  f"{cell}: nn correlation {mean} vs Onsager {exact}")
            finals[cell] = np.asarray(res.final_words)
    return finals


def check_parity(finals: dict) -> None:
    """Print, per cell, the final-state words that differ between pallas
    and scan; any difference fails (parity is exact on the chip)."""
    import numpy as np

    for cell, words in finals.items():
        if not cell.endswith("/pallas"):
            continue
        base = cell[: -len("/pallas")]
        n = int(np.count_nonzero(words != finals[base + "/scan"]))
        log(f"parity {base}: pallas vs scan differ in {n} of {words.size} "
            f"final-state words")
        check(n == 0, f"parity {base}: pallas and scan differ in {n} words")


def _requests(n: int, steps: dict | None = None):
    """A mixed burst alternating ising and gmm; ``steps`` maps a workload
    to its step budget (default: the workload's own)."""
    from repro.serving import ServeRequest

    steps = steps or {}
    names = ("ising", "gmm")
    return [
        ServeRequest(rid=i, workload=names[i % 2], seed=100 + i,
                     n_steps=steps.get(names[i % 2]))
        for i in range(n)
    ]


def _workload_kwargs() -> dict:
    return dict(
        height=SIDE, width=SIDE, batch=LATTICES,
        chains=GMM_CHAINS, beta=BETA,
    )


def solo_matches(sched, req) -> bool:
    """Whether a served request equals its solo ``engine.submit`` run."""
    import numpy as np

    from repro.samplers import RunPlan

    m = sched.executor_for(req.workload).member_for(req.workload)
    init, k_run, n = m.request_init(req)
    res = m.engine.submit(
        RunPlan(target=m.target, n_steps=n, init_words=init, key=k_run,
                collect=req.collect)
    ).result
    return bool(
        np.array_equal(np.asarray(res.final_words), req.final_words)
        and np.array_equal(np.asarray(res.accept_count), req.accept_count)
    )


def run_serving(execution: str):
    import jax
    import numpy as np

    from repro.serving import Scheduler, latency_summary

    sched = Scheduler(
        n_slots=SLOTS, randomness="fused", execution=execution,
        smoke=False, chunk_steps=CHUNK_STEPS,
        workload_kwargs=_workload_kwargs(),
    )
    reqs = _requests(REQUESTS)
    t0 = time.perf_counter()
    done = sched.serve(reqs)
    dt = time.perf_counter() - t0
    check(len(done) == len(reqs) and all(r.t_done is not None for r in done),
          f"serving/{execution}: {len(done)} of {len(reqs)} finished")
    for r in done:
        check(np.isfinite(r.acceptance_rate) and r.final_words is not None,
              f"serving/{execution}: request {r.rid} has no result")
    summary = latency_summary(done)
    log(
        f"serving/{execution}: shape_classes={sched.shape_classes} "
        f"compiled_programs={sched.compiled_programs} "
        f"requests/s={len(done) / dt:.4g} p50_latency_s="
        f"{summary['p50_latency_s']} (wall {dt:.3f} s incl. compile, "
        f"{device_label()})"
    )
    if execution == "pallas":
        for ex in sched.executors.values():
            words = jax.ShapeDtypeStruct(ex.words.shape, ex.words.dtype)
            keys = jax.ShapeDtypeStruct((ex.n_slots, 2), np.uint32)
            step0s = jax.ShapeDtypeStruct((ex.n_slots,), np.int32)
            text = ex._advance.lower(
                words, keys, step0s, seg=CHUNK_STEPS, collect="last"
            ).as_text()
            check("tpu_custom_call" in text,
                  f"serving/pallas/{ex.members[0].name}: no tpu_custom_call")
    by_rid = {r.rid: r for r in done}
    picks = [0, 1, len(reqs) - 2]  # ising, gmm, and a second-wave ising
    for rid in picks:
        check(solo_matches(sched, by_rid[rid]),
              f"serving/{execution}: request {rid} != its solo run")
    log(f"serving/{execution}: requests {picks} equal their solo "
        f"engine.submit runs")
    return {r.rid: r.final_words for r in done}


def run_one_chip() -> None:
    import numpy as np

    t0 = time.perf_counter()
    finals = run_batch()
    log(f"batch phase done in {time.perf_counter() - t0:.1f} s")
    check_parity(finals)
    t0 = time.perf_counter()
    served = {ex: run_serving(ex) for ex in ("pallas", "scan")}
    n = sum(
        int(np.count_nonzero(served["pallas"][rid] != served["scan"][rid]))
        for rid in served["pallas"]
    )
    log(f"parity serving: pallas vs scan differ in {n} final-state words "
        f"over {len(served['pallas'])} requests")
    check(n == 0, f"parity serving: pallas and scan differ in {n} words")
    log(f"serving phase done in {time.perf_counter() - t0:.1f} s")


# --- four chips -------------------------------------------------------------


def _sharded_over(x, n: int) -> bool:
    sh = x.sharding
    return len(sh.device_set) == n and not sh.is_fully_replicated


def run_four_chips() -> None:
    import jax
    import numpy as np

    from repro import workloads
    from repro.launch.mesh import make_chains_mesh
    from repro.serving import Scheduler

    check(len(jax.devices()) == N_DEVICES,
          f"--four-chips needs {N_DEVICES} devices, found {len(jax.devices())}")
    mesh = make_chains_mesh(MESH_CHAINS)
    for execution in ("pallas", "scan"):
        cell = f"ising/fused/{execution}/chains={MESH_CHAINS}"
        wl = workloads.build(
            "ising", jax.random.PRNGKey(5), randomness="fused",
            backend=execution, height=SIDE, width=SIDE,
            batch=MESH_LATTICES, n_steps=MESH_STEPS,
            chunk_steps=CHUNK_STEPS, num_chains=MESH_CHAINS,
            collect="last", beta=BETA,
        )
        key = jax.random.PRNGKey(6)
        t0 = time.perf_counter()
        sharded = wl.engine.submit(wl.plan(key, mesh=mesh)).result
        jax.block_until_ready(sharded.final_words)
        t_sharded = time.perf_counter() - t0
        t0 = time.perf_counter()
        single = wl.engine.submit(wl.plan(key)).result
        jax.block_until_ready(single.final_words)
        t_single = time.perf_counter() - t0
        check(_sharded_over(sharded.final_words, N_DEVICES),
              f"{cell}: final words not sharded over {N_DEVICES} devices "
              f"({sharded.final_words.sharding})")
        same = all(
            np.array_equal(np.asarray(getattr(sharded, f)),
                           np.asarray(getattr(single, f)))
            for f in ("final_words", "accept_count", "final_logp")
        )
        log(
            f"{cell}: sharded over {N_DEVICES} devices == one device: "
            f"{same} (wall {t_sharded:.3f} s sharded, {t_single:.3f} s one "
            f"device, incl. compile, {device_label()})"
        )
        check(same, f"{cell}: sharded run differs from one device")

    results = {}
    for label, m in (("mesh", make_chains_mesh()), ("one", None)):
        sched = Scheduler(
            n_slots=SLOTS, randomness="fused", execution="scan",
            smoke=False, chunk_steps=CHUNK_STEPS,
            workload_kwargs=_workload_kwargs(), mesh=m,
        )
        reqs = _requests(
            SLOTS, {"ising": MESH_STEPS, "gmm": 2 * MESH_STEPS}
        )
        t0 = time.perf_counter()
        done = sched.serve(reqs)
        dt = time.perf_counter() - t0
        check(len(done) == len(reqs), f"serving/{label}: requests lost")
        if m is not None:
            words = next(iter(sched.executors.values())).words
            check(_sharded_over(words, N_DEVICES),
                  f"serving/mesh: slot pool not sharded ({words.sharding})")
        results[label] = {r.rid: r for r in done}
        log(f"serving/scan/{label}: {len(done)} requests in {dt:.3f} s "
            f"incl. compile ({device_label()})")
    same = all(
        np.array_equal(results["mesh"][rid].final_words,
                       results["one"][rid].final_words)
        and np.array_equal(results["mesh"][rid].accept_count,
                           results["one"][rid].accept_count)
        for rid in results["one"]
    )
    log(f"serving/scan: slot-sharded over {N_DEVICES} devices == one "
        f"device: {same}")
    check(same, "serving/scan: sharded burst differs from one device")


# --- entry ------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded path on four chips, against one device",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, but JAX found platform {platform!r} "
            f"({len(devices)} device(s)); nothing was run",
            file=sys.stderr,
        )
        return 2

    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            run_four_chips()
        else:
            run_one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
