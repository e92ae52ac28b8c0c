"""Serve concurrent MCMC sampling requests on the packed chain engine.

The serving face of the sampler (DESIGN.md §Serving): heterogeneous
requests — each a (workload, n_steps, seed, collect) tuple — are packed
into the chain axis of one engine program by ``repro.serving``.
Admission and retirement happen between ``chunk_steps`` segments via the
engine's ``step0`` resume axis, so every request's sample stream is
bit-identical to its solo ``launch.sample``-style run no matter when it
joined or who shared the batch.

Requests come from a JSONL spec (one object per line with any of
``rid / workload / n_steps / seed / collect / t_arrive``) or from a
synthetic Poisson arrival generator (``--poisson-rate`` arrivals/s,
seeds 0..N-1).  Arrival gaps are fast-forwarded by default; pass
``--realtime`` to sleep through them.

``--workload`` takes a comma-separated list for a mixed burst
(round-robin assignment): under scan execution every uint32 workload
shares ONE compiled shape-class program; under pallas each workload
geometry gets one packed kernel grid over all its slots.  ``--mesh``
shards the slot axis over all addressable devices (scan only).

Usage:
  PYTHONPATH=src python -m repro.launch.serve_engine --smoke \
      --requests 6 --slots 3 --poisson-rate 50
  PYTHONPATH=src python -m repro.launch.serve_engine --smoke \
      --workload gmm --requests 8 --slots 4 --randomness fused \
      --collect thin:4
  PYTHONPATH=src python -m repro.launch.serve_engine --smoke \
      --workload ising,gmm --backend pallas --randomness fused \
      --slots 4 --requests 6
  PYTHONPATH=src python -m repro.launch.serve_engine --spec requests.jsonl

Per-request lines report wait/latency and the accept (MH) or flip
(Gibbs) rate; the footer is the ``latency_summary`` row (requests/s,
p50/p99 latency) that ``benchmarks.bench_serving`` tables.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from repro import telemetry, workloads
from repro.serving import Scheduler, ServeRequest, latency_summary


def _workload_list(value: str) -> list[str]:
    names = [w.strip() for w in value.split(",") if w.strip()]
    if not names:
        raise argparse.ArgumentTypeError("empty workload list")
    for name in names:
        if name not in workloads.WORKLOADS:
            raise argparse.ArgumentTypeError(
                f"unknown workload {name!r} (choices: "
                f"{', '.join(sorted(workloads.WORKLOADS))})"
            )
    return names


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="repro.launch.serve_engine",
        description="Serve sampling requests packed into one engine program.",
    )
    p.add_argument(
        "--workload", default=["ising"], type=_workload_list,
        help="workload for synthetic requests, or a comma-separated list "
        "(round-robin assignment) for a mixed burst; JSONL specs name "
        "their own.  Choices: " + ", ".join(sorted(workloads.WORKLOADS)),
    )
    p.add_argument(
        "--randomness", default="cim", choices=("host", "cim", "fused")
    )
    p.add_argument(
        "--backend", default="scan", choices=("auto", "scan", "pallas"),
        help="engine execution: scan packs every uint32 workload into ONE "
        "vmapped shape-class program (per-slot lax.switch dispatch, "
        "traced step0); pallas folds all slots into one batched "
        "fused-kernel grid per workload geometry (per-slot operand step0)",
    )
    p.add_argument(
        "--mesh", action="store_true",
        help="shard the slot axis over all addressable devices through "
        "the 'chains' sharding rule (scan backend only; no-op on a "
        "single device)",
    )
    p.add_argument("--smoke", action="store_true", help="tiny sizes for CI")
    p.add_argument("--slots", type=int, default=4, help="packed slot pool")
    p.add_argument(
        "--requests", type=int, default=8,
        help="synthetic request count (overflow waits in the FIFO)",
    )
    p.add_argument(
        "--steps", type=int, default=None,
        help="steps per synthetic request (default: workload default)",
    )
    p.add_argument(
        "--collect", default="last",
        help="collection mode for synthetic requests: all | thin:<k> | "
        "last (the serving default — O(state) memory)",
    )
    p.add_argument(
        "--chunk-steps", type=int, default=None,
        help="admission/retirement granularity (default: engine chunk)",
    )
    p.add_argument(
        "--autotune", action="store_true",
        help="measure chunk_steps for the workload template before "
        "serving (samplers.autotune; cached per workload/shape/device)",
    )
    p.add_argument(
        "--autotune-cache", default=None, metavar="PATH",
        help="autotune cache file (default: $REPRO_AUTOTUNE_CACHE or "
        "~/.cache/repro/autotune.json)",
    )
    p.add_argument(
        "--poisson-rate", type=float, default=0.0,
        help="mean synthetic arrivals/s (0 = all requests arrive at t=0)",
    )
    p.add_argument(
        "--spec", default=None, metavar="PATH",
        help="JSONL request spec; overrides the synthetic generator",
    )
    p.add_argument(
        "--realtime", action="store_true",
        help="sleep through arrival gaps instead of fast-forwarding",
    )
    p.add_argument("--seed", type=int, default=0, help="arrival-process seed")
    # telemetry + SLO health (DESIGN.md §Telemetry)
    p.add_argument(
        "--trace", default=None, metavar="PATH",
        help="record host-side trace spans and export on exit "
        "(*.json/*.trace -> Chrome-trace, else JSONL)",
    )
    p.add_argument(
        "--metrics", default=None, metavar="PATH",
        help="flush metrics snapshots: *.prom/*.txt -> final Prometheus "
        "text, anything else -> periodic JSONL lines from the serve loop",
    )
    p.add_argument(
        "--metrics-interval", type=float, default=5.0,
        help="seconds between periodic JSONL metrics flushes",
    )
    p.add_argument(
        "--slo-p99", type=float, default=None, metavar="SECONDS",
        help="p99 end-to-end latency SLO; breach prints a [health] line",
    )
    p.add_argument(
        "--slo-wait", type=float, default=None, metavar="SECONDS",
        help="p99 queue-wait SLO; breach prints a [health] line",
    )
    return p


def load_spec(path: str) -> list[ServeRequest]:
    """Requests from a JSONL file, one object per line; missing fields
    take the ``ServeRequest`` defaults, ``rid`` defaults to the line
    number."""
    requests = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            obj.setdefault("rid", i)
            requests.append(ServeRequest(**obj))
    return requests


def poisson_requests(args) -> list[ServeRequest]:
    """N synthetic requests with Poisson arrivals (exponential gaps at
    ``--poisson-rate``; rate 0 = a burst at t=0) and seeds 0..N-1."""
    rng = np.random.default_rng(args.seed)
    t = 0.0
    requests = []
    names = args.workload
    for rid in range(args.requests):
        if args.poisson_rate > 0:
            t += float(rng.exponential(1.0 / args.poisson_rate))
        requests.append(
            ServeRequest(
                rid=rid,
                workload=names[rid % len(names)],  # round-robin mixed burst
                n_steps=args.steps,
                seed=rid,
                collect=args.collect,
                t_arrive=t,
            )
        )
    return requests


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    requests = (
        load_spec(args.spec) if args.spec else poisson_requests(args)
    )
    chunk_steps = args.chunk_steps
    if args.autotune and chunk_steps is None:
        # tune the segment granularity on the workload template (the
        # executor group's engine/target pair); execution stays as the
        # --backend pin — the serving tier's pack-vs-solo dispatch is
        # chosen there, not by throughput alone
        import jax

        from repro import samplers

        wl = workloads.build(
            args.workload[0], jax.random.PRNGKey(0),
            randomness=args.randomness, smoke=args.smoke,
        )
        cfg = wl.engine.config
        if args.backend in ("scan", "pallas"):
            import dataclasses

            cfg = dataclasses.replace(cfg, execution=args.backend)
        _, tuned = samplers.autotune_config(
            cfg, wl.target, wl.init_words, cache_path=args.autotune_cache
        )
        chunk_steps = tuned.chunk_steps
        print(
            f"[serve_engine] autotune: chunk_steps={chunk_steps} "
            f"({tuned.source}, {tuned.steps_per_s:.3g} site-steps/s vs "
            f"incumbent {tuned.baseline_steps_per_s:.3g})"
        )
    if args.trace:
        telemetry.enable()
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_chains_mesh

        mesh = make_chains_mesh()
        if mesh is None:
            print("[serve_engine] --mesh: single device, serving unsharded")
    sched = Scheduler(
        n_slots=args.slots,
        randomness=args.randomness,
        execution=args.backend,
        smoke=args.smoke,
        chunk_steps=chunk_steps,
        mesh=mesh,
    )
    if args.metrics and not args.metrics.endswith((".prom", ".txt")):
        sched.metrics_flusher = telemetry.JsonlFlusher(
            telemetry.REGISTRY, args.metrics,
            interval_s=args.metrics_interval,
        )
    done = sched.serve(requests, realtime=args.realtime)
    for r in sorted(done, key=lambda r: r.rid):
        n_kept = 0 if r.samples is None else r.samples.shape[0]
        print(
            f"  req {r.rid}: workload={r.workload} steps="
            f"{r.n_steps or 'default'} collect={r.collect} kept={n_kept} "
            f"wait_s={r.wait_s:.3f} service_s={r.service_s:.3f} "
            f"latency_s={r.latency_s:.3f} "
            f"{r.rate_label}={r.acceptance_rate:.4f}"
        )
    summary = latency_summary(done)
    row = {
        "slots": args.slots,
        "randomness": args.randomness,
        "backend": args.backend,
        "shape_classes": sched.shape_classes,
        "compiled_programs": sched.compiled_programs,
        **summary,
    }
    print("[serve_engine] " + "  ".join(f"{k}={v}" for k, v in row.items()))
    monitor = telemetry.HealthMonitor(
        telemetry.HealthThresholds(
            p99_latency_slo_s=args.slo_p99, max_wait_slo_s=args.slo_wait
        ),
        warn=False,
    )
    monitor.check_serving(summary, where=",".join(args.workload))
    for alert in monitor.alerts:
        print(f"[health] {alert.severity} {alert.kind}: {alert.message}")
    if args.trace:
        n = telemetry.TRACER.export(args.trace)
        print(f"[trace] wrote {n} events to {args.trace}")
        telemetry.disable()
    if args.metrics:
        if args.metrics.endswith((".prom", ".txt")):
            with open(args.metrics, "w") as f:
                f.write(telemetry.REGISTRY.prometheus_text())
        else:
            sched.metrics_flusher.close()
        print(f"[metrics] wrote snapshot to {args.metrics}")
    return row


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    main()
