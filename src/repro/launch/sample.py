"""Run a workload from the probabilistic-model zoo and report diagnostics.

The non-LLM face of the sampler engine: pick a workload from the
registry (2-D Ising/MRF via checkerboard Gibbs, GMM posterior via MH,
±J spin glass), a randomness backend (ideal host vs the paper's CIM
pipeline), and an execution substrate (scan vs the fused Pallas kernel),
run the chains, and print throughput plus chain diagnostics
(flip/acceptance rate, integrated autocorrelation time, ESS,
split-R-hat).

Usage:
  PYTHONPATH=src python -m repro.launch.sample --workload ising --smoke \
      --randomness cim --backend scan
  PYTHONPATH=src python -m repro.launch.sample --workload gmm \
      --chains 64 --steps 2048 --backend pallas
  PYTHONPATH=src python -m repro.launch.sample --workload ising \
      --num-chains 8 --backend pallas

  # long chain, keep every 16th sample (diagnostics on the kept stream)
  PYTHONPATH=src python -m repro.launch.sample --workload ising \
      --steps 20000 --thin 16
  # optimisation-style run: O(state) sample memory, rate-only output
  PYTHONPATH=src python -m repro.launch.sample --workload spin_glass \
      --steps 50000 --keep-last

Workload choices and their knobs come straight from the
``workloads.WORKLOADS`` registry (flags a builder doesn't accept are
simply not forwarded), so a newly registered workload appears here with
no CLI change.

``--num-chains C`` runs C independent chains in one device program
(DESIGN.md §Chains-axis); with more than one device visible the chain
axis shards over a 1-D mesh via shard_map (bit-identical to unsharded).

Tempering (DESIGN.md §Tempering) wraps the same workload target:

  # parallel tempering: 8 replicas, geometric ladder down to beta 0.25
  PYTHONPATH=src python -m repro.launch.sample --workload spin_glass \
      --smoke --ladder 8 --beta-min 0.25 --swap-every 16

  # simulated annealing to a ground state / MAX-CUT
  PYTHONPATH=src python -m repro.launch.sample --workload spin_glass \
      --smoke --anneal 8 --beta-min 0.4 --beta-max 4.0

Both print swap/round-trip diagnostics (ladder) or the best-ever energy
(anneal) next to the cold-chain sample diagnostics; tempered streams are
bit-identical across {scan, pallas} x chunkings (tests/test_tempering).
"""

from __future__ import annotations

import argparse
import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import diagnostics, samplers, telemetry, tempering, workloads
from repro.core import energy
from repro.launch.mesh import make_chains_mesh


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="repro.launch.sample",
        description="Sample a zoo workload on the unified engine.",
    )
    p.add_argument(
        "--workload", required=True, choices=sorted(workloads.WORKLOADS)
    )
    p.add_argument(
        "--randomness", default="cim", choices=("host", "cim", "fused"),
        help="operand source: host jax.random, the CIM pseudo-read+MSXOR "
        "pipeline, or fused in-kernel counter RNG (zero operand traffic "
        "under --backend pallas; DESIGN.md §Randomness)",
    )
    p.add_argument(
        "--backend", default="auto", choices=("auto", "scan", "pallas")
    )
    p.add_argument(
        "--smoke", action="store_true", help="tiny sizes for CPU CI runs"
    )
    p.add_argument("--steps", type=int, default=None, help="chain steps")
    p.add_argument(
        "--num-chains", type=int, default=1,
        help="independent chains run in one device program",
    )
    # collection axis (DESIGN.md §Collection) — mutually exclusive
    coll = p.add_mutually_exclusive_group()
    coll.add_argument(
        "--thin", type=int, default=None, metavar="K",
        help="keep every K-th absolute step (engine collect='thin:K'); "
        "diagnostics run on the kept stream",
    )
    coll.add_argument(
        "--keep-last", action="store_true",
        help="keep only the final state (engine collect='last'): O(state) "
        "sample memory for any chain length; series diagnostics skipped",
    )
    p.add_argument("--seed", type=int, default=0)
    # lattice knobs (ising / spin_glass)
    p.add_argument("--height", type=int, default=None, help="lattice H")
    p.add_argument("--width", type=int, default=None, help="lattice W")
    p.add_argument("--batch", type=int, default=None, help="lattices")
    p.add_argument("--beta", type=float, default=None, help="ising coupling")
    p.add_argument("--field", type=float, default=0.0, help="external field")
    p.add_argument(
        "--maxcut", action="store_true",
        help="spin_glass: signed MAX-CUT couplings (J = -w); tempered "
        "rows then report best_cut",
    )
    # gmm knobs
    p.add_argument("--nbits", type=int, default=None, help="gmm grid bits")
    p.add_argument("--chains", type=int, default=None, help="gmm chains")
    # tempering (repro/tempering, DESIGN.md §Tempering)
    p.add_argument(
        "--ladder", type=int, default=0, metavar="R",
        help="parallel tempering with R replicas on a geometric ladder",
    )
    p.add_argument(
        "--swap-every", type=int, default=16,
        help="replica-exchange period in engine steps",
    )
    p.add_argument(
        "--anneal", type=int, default=0, metavar="S",
        help="simulated annealing over S geometric cooling stages",
    )
    p.add_argument(
        "--autotune", action="store_true",
        help="replace the hand-chosen chunk_steps/block_c/backend with "
        "the measured per-(workload, shape, device) winner (cached; "
        "DESIGN.md §Run-API)",
    )
    p.add_argument(
        "--autotune-cache", default=None, metavar="PATH",
        help="autotune cache file (default $REPRO_AUTOTUNE_CACHE or "
        "~/.cache/repro/autotune.json)",
    )
    p.add_argument(
        "--beta-min", type=float, default=0.25,
        help="hottest ladder beta / annealing start beta",
    )
    p.add_argument(
        "--beta-max", type=float, default=4.0,
        help="annealing end beta (annealing only; ladders end at 1.0)",
    )
    # telemetry (DESIGN.md §Telemetry)
    p.add_argument(
        "--trace", default=None, metavar="PATH",
        help="record host-side trace spans and export on exit: "
        "*.json/*.trace -> Chrome-trace (chrome://tracing / Perfetto), "
        "anything else -> JSONL (validate/summarize with "
        "python -m repro.launch.monitor)",
    )
    p.add_argument(
        "--metrics", default=None, metavar="PATH",
        help="write the final metrics snapshot: *.prom/*.txt -> "
        "Prometheus exposition text, anything else -> one JSONL line",
    )
    return p


def _export_telemetry(args) -> None:
    if args.trace:
        n = telemetry.TRACER.export(args.trace)
        print(f"[trace] wrote {n} events to {args.trace}")
        telemetry.disable()
    if args.metrics:
        if args.metrics.endswith((".prom", ".txt")):
            with open(args.metrics, "w") as f:
                f.write(telemetry.REGISTRY.prometheus_text())
        else:
            telemetry.REGISTRY.flush_jsonl(args.metrics)
        print(f"[metrics] wrote snapshot to {args.metrics}")


def _collect_arg(args) -> str:
    """The engine collection spec the CLI flags select."""
    if args.thin is not None:
        if args.thin < 1:
            raise SystemExit(f"--thin must be >= 1, got {args.thin}")
        return f"thin:{args.thin}"
    return "last" if args.keep_last else "all"


def _workload_kwargs(args) -> dict:
    """Forward exactly the flags the registered builder accepts — the
    registry, not this module, decides a workload's knobs."""
    candidates = dict(
        randomness=args.randomness,
        backend=args.backend,
        smoke=args.smoke,
        n_steps=args.steps,
        num_chains=args.num_chains,
        collect=_collect_arg(args),
        height=args.height,
        width=args.width,
        batch=args.batch,
        beta=args.beta,
        field=args.field,
        maxcut=args.maxcut,
        nbits=args.nbits,
        chains=args.chains,
    )
    params = inspect.signature(workloads.WORKLOADS[args.workload]).parameters
    return {k: v for k, v in candidates.items() if k in params}


def _rate_key(wl) -> str:
    """The workload owns the canonical rate label (DESIGN.md §2)."""
    return wl.rate_key


def _series_diagnostics(wl, samples) -> dict:
    """Post-burn-in diagnostics of the workload statistic over one
    (solo-shaped) sample block."""
    series = np.asarray(wl.series_fn(samples))
    series = series.reshape(series.shape[0], -1)
    return diagnostics.summarize(series[wl.burn_in:])


def _run_ladder(args, wl, k_run, monitor) -> dict:
    ladder = tempering.Ladder.geometric(args.ladder, beta_min=args.beta_min)
    rex = tempering.ReplicaExchange(
        ladder=ladder, engine=wl.engine, swap_every=args.swap_every
    )
    init = jnp.broadcast_to(
        wl.init_words, (ladder.num_replicas, *wl.init_words.shape)
    )
    t0 = time.time()
    result = rex.run(k_run, wl.target, wl.n_steps, init)
    jax.block_until_ready(result.samples)
    wall_s = time.time() - t0

    site_steps = wl.n_steps * int(init.size)
    diag = _series_diagnostics(wl, result.cold_samples)
    monitor.check_acceptance(
        float(result.acceptance_rate), label=_rate_key(wl), where=wl.name
    )
    monitor.check_swap_stats(result.swap, where=wl.name)
    monitor.check_chain_stats(diag, where=wl.name)
    row = {
        "mode": "ladder",
        "num_replicas": ladder.num_replicas,
        "swap_every": args.swap_every,
        "beta_min": round(min(ladder.betas), 4),
        "n_steps": wl.n_steps,
        "wall_s": round(wall_s, 3),
        "site_steps_per_s": round(site_steps / max(wall_s, 1e-9), 1),
        _rate_key(wl): round(float(result.acceptance_rate), 4),
        **result.swap.summary(),
        # sample quality of the cold (beta = betas[0]) replica; its
        # post-burn-in step count is kept_steps, as in the plain rows
        **{
            ("kept_steps" if k == "n_steps" else k): v
            for k, v in diag.items()
        },
    }
    if getattr(wl.target, "maxcut_reduction", False):
        # best cut the target-measure replica ever visited
        row["best_cut"] = round(
            float(np.asarray(wl.target.cut_value(result.cold_samples)).max()),
            4,
        )
    return row


def _run_anneal(args, wl, k_run, monitor) -> dict:
    annealer = tempering.Annealer.geometric(
        args.anneal,
        max(1, wl.n_steps // args.anneal),
        beta_min=args.beta_min,
        beta_max=args.beta_max,
    )
    t0 = time.time()
    result = annealer.run(k_run, wl.target, wl.init_words, engine=wl.engine)
    jax.block_until_ready(result.best_words)
    wall_s = time.time() - t0

    site_steps = result.n_steps * int(wl.init_words.size)
    best_logp = np.asarray(result.best_logp)
    monitor.check_acceptance(
        float(result.acceptance_rate), label=_rate_key(wl), where=wl.name
    )
    row = {
        "mode": "anneal",
        "stages": args.anneal,
        "beta_min": round(min(annealer.betas), 4),
        "beta_max": round(max(annealer.betas), 4),
        "n_steps": result.n_steps,
        "wall_s": round(wall_s, 3),
        "site_steps_per_s": round(site_steps / max(wall_s, 1e-9), 1),
        _rate_key(wl): round(float(result.acceptance_rate), 4),
        # lattice targets: best_logp is -energy, report the best energy
        "best_energy": round(float(-best_logp.max()), 4),
    }
    if getattr(wl.target, "maxcut_reduction", False):
        row["best_cut"] = round(
            float(np.asarray(wl.target.cut_value(result.best_words)).max()), 4
        )
    return row


def main(argv=None) -> dict:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.ladder and args.anneal:
        parser.error("--ladder and --anneal are mutually exclusive")
    if (args.ladder or args.anneal) and args.num_chains > 1:
        parser.error(
            "--ladder/--anneal occupy the engine's chain-id axis; batch "
            "the workload (e.g. --batch/--chains) for parallel ensembles"
        )
    if (args.ladder or args.anneal) and (
        args.thin is not None or args.keep_last
    ):
        parser.error(
            "--thin/--keep-last apply to plain runs; the tempering "
            "drivers consume the full segment streams for their own "
            "diagnostics/best-state tracking"
        )
    if args.trace:
        telemetry.enable()
    monitor = telemetry.HealthMonitor(warn=False)
    key = jax.random.PRNGKey(args.seed)
    k_init, k_run = jax.random.split(key)
    wl = workloads.build(args.workload, k_init, **_workload_kwargs(args))

    base = {
        "workload": wl.name,
        "update": wl.engine.config.update,
        "randomness": args.randomness,
        "backend": args.backend,
        "collect": _collect_arg(args),
    }
    if args.autotune:
        wl.engine, tuned = samplers.autotune_engine(
            wl.engine, wl.target, wl.init_words,
            cache_path=args.autotune_cache,
        )
        base["backend"] = tuned.execution
        base["autotune"] = (
            f"chunk{tuned.chunk_steps}:{tuned.execution} ({tuned.source}, "
            f"{tuned.steps_per_s / max(tuned.baseline_steps_per_s, 1e-9):.2f}x"
            " vs incumbent)"
        )
    if args.ladder:
        row = {**base, **_run_ladder(args, wl, k_run, monitor)}
    elif args.anneal:
        row = {**base, **_run_anneal(args, wl, k_run, monitor)}
    else:
        mesh = make_chains_mesh(args.num_chains)
        t0 = time.time()
        result = wl.run(k_run, mesh=mesh)
        jax.block_until_ready(result.samples)
        wall_s = time.time() - t0

        diag = wl.diagnostics(result)
        monitor.check_acceptance(
            float(result.acceptance_rate), label=_rate_key(wl), where=wl.name
        )
        monitor.check_chain_stats(diag, where=wl.name)
        n_sites = int(wl.init_words.size)
        site_steps = wl.n_steps * n_sites
        nbits = int(wl.meta.get("nbits", wl.target.nbits))
        macro_fj = energy.energy_per_sample_fj(
            float(result.acceptance_rate), nbits
        ) * site_steps

        row = {
            **base,
            "n_steps": wl.n_steps,
            "burn_in": wl.burn_in,
            "n_sites": n_sites,
            "wall_s": round(wall_s, 3),
            "site_steps_per_s": round(site_steps / max(wall_s, 1e-9), 1),
            "macro_energy_pj": round(macro_fj * 1e-3, 2),
            **{k: v for k, v in wl.meta.items() if k != "nbits"},
            # diagnostics run on the post-burn-in series; disambiguate
            # its step count from the chain's
            **{
                ("kept_steps" if k == "n_steps" else k): v
                for k, v in diag.items()
            },
        }
    print("  ".join(f"{k}={v}" for k, v in row.items()))
    for alert in monitor.alerts:
        print(f"[health] {alert.severity} {alert.kind}: {alert.message}")
    _export_telemetry(args)
    return row


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    main()
