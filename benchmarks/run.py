"""Benchmark harness: one module per paper table/figure or subsystem.

Each module exposes ``run() -> list[dict]``; this driver executes them
all, prints per-table key=value lines (machine-greppable,
human-readable), and aggregates every table into ``BENCH_workloads.json``
at the repo root so the perf trajectory stays machine-readable across
PRs (rows are merged table-by-table, so a filtered run refreshes only
the tables it executed).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig17      # name filter
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI bench-smoke job

``--smoke`` runs only the modules that expose tiny presets
(``run(smoke=True)``), writes their tables under a ``_smoke`` suffix —
so a smoke run never clobbers the full-size rows — and is what the CI
bench job regenerates and gates via ``benchmarks.check_regression``.
``--out`` redirects the aggregate (CI writes a fresh file and compares
it against the committed baseline).
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import time

MODULES = [
    ("fig4_bfr", "benchmarks.table_fig4_bfr"),
    ("fig9_msxor", "benchmarks.table_fig9_msxor"),
    ("fig15_thermal", "benchmarks.table_fig15_thermal"),
    ("fig16a_energy", "benchmarks.table_fig16_energy"),
    ("fig16b_throughput", "benchmarks.table_fig16b_throughput"),
    ("fig17_sampling", "benchmarks.table_fig17_sampling"),
    ("kernels", "benchmarks.bench_kernels"),
    ("sampler_quality", "benchmarks.bench_sampler_quality"),
    ("token_sampler", "benchmarks.bench_token_sampler"),
    ("gray_ablation", "benchmarks.bench_gray_ablation"),
    ("workloads", "benchmarks.bench_workloads"),
    ("autotune", "benchmarks.bench_autotune"),
    ("chain_scaling", "benchmarks.bench_chain_scaling"),
    ("tempering", "benchmarks.bench_tempering"),
    ("collection", "benchmarks.bench_collection"),
    ("serving", "benchmarks.bench_serving"),
    ("telemetry", "benchmarks.bench_telemetry"),
]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGGREGATE_PATH = os.path.join(_REPO_ROOT, "BENCH_workloads.json")


def _supports_smoke(run_fn) -> bool:
    return "smoke" in inspect.signature(run_fn).parameters


def write_aggregate(tables: dict, path: str = AGGREGATE_PATH) -> None:
    """Merge the tables that ran into the cross-PR aggregate file."""
    merged = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                merged = json.load(f).get("tables", {})
        except (json.JSONDecodeError, OSError):
            merged = {}  # corrupt/legacy file: rebuild from this run
    merged.update(tables)
    with open(path, "w") as f:
        json.dump({"format": 1, "tables": merged}, f, indent=2, sort_keys=True)
        f.write("\n")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="benchmarks.run", description="Run the benchmark tables."
    )
    p.add_argument("filter", nargs="?", default="", help="table-name filter")
    p.add_argument(
        "--smoke", action="store_true",
        help="tiny presets; only smoke-capable modules; *_smoke table names",
    )
    p.add_argument(
        "--out", default=AGGREGATE_PATH,
        help=f"aggregate JSON path (default {AGGREGATE_PATH})",
    )
    p.add_argument(
        "--trace-dir", default=None, metavar="DIR",
        help="record a telemetry trace per bench module and export "
        "DIR/<table>.trace.jsonl artifacts (summarize/validate with "
        "python -m repro.launch.monitor)",
    )
    return p


def _export_module_trace(trace_dir: str, name: str) -> None:
    """One trace artifact per bench module, plus the compile/steady
    split its engine.submit spans carry (printed, not tabled — the
    gated compile_s/steady_s fields live in the telemetry table)."""
    from repro import telemetry

    path = os.path.join(trace_dir, f"{name}.trace.jsonl")
    events = telemetry.TRACER.events()
    n = telemetry.TRACER.export_jsonl(path)
    submit = [
        e for e in events if e.kind == "span" and e.name == "engine.submit"
    ]
    compile_s = sum(
        e.dur_us for e in submit if e.meta.get("jit_cache") == "miss"
    ) / 1e6
    steady_s = sum(
        e.dur_us for e in submit if e.meta.get("jit_cache") != "miss"
    ) / 1e6
    print(
        f"  [trace] {n} events -> {path} (submit compile_s="
        f"{compile_s:.3f} steady_s={steady_s:.3f})"
    )


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
    failures = []
    tables = {}
    for name, modpath in MODULES:
        if args.filter and args.filter not in name:
            continue
        print(f"\n=== {name} ({modpath}) ===")
        t0 = time.time()
        try:
            mod = __import__(modpath, fromlist=["run"])
            if args.smoke:
                if not _supports_smoke(mod.run):
                    print("  [skipped: no smoke presets]")
                    continue
                name = f"{name}_smoke"
            if args.trace_dir:
                from repro import telemetry

                telemetry.enable()  # reset: one trace per module
            rows = mod.run(smoke=True) if args.smoke else mod.run()
            for row in rows:
                print("  " + "  ".join(f"{k}={v}" for k, v in row.items()))
            print(f"  [{len(rows)} rows, {time.time() - t0:.1f}s]")
            if args.trace_dir:
                _export_module_trace(args.trace_dir, name)
            tables[name] = rows
        except Exception as e:  # keep the harness going; report at the end
            import traceback

            traceback.print_exc()
            failures.append((name, repr(e)))
    if tables:
        write_aggregate(tables, path=args.out)
        print(f"\naggregated {len(tables)} tables -> {args.out}")
    if failures:
        print("\nFAILED:", failures)
        raise SystemExit(1)
    print("\nall benchmarks completed")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    main()
