"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name (``BENCHMARK.json`` at the
repository root names the cell's configuration and traffic):

* ``configs/<config>.json``: the deployment's sizes, source and stream
  contract; ``configs/<config>.py`` beside it: the plain reference, the
  comparison and the work counts (updates, Threefry blocks, bytes);
* ``traffic/<traffic>.json``: parameters read by the general generator
  of its ``kind``, ``kinds/<kind>.py`` (``batch`` jobs or ``serve``
  requests);
* ``limits/<cell>.json``: the limit of each number compared;
* ``metrics/<metric>.py``: one reader per per-layer metric.

The run builds the cell's state on the device from ``--seed``, warms up
the cell's own shapes (set-up), then measures for ``--seconds``.  With
``--trace 1`` the window runs under ``jax.profiler`` (a batch cell's for
its first ``TRACE_SECONDS``) and the run reports the per-layer metrics
instead of the end-to-end ones.  After the window
the reference checks what the window produced; each number compared is
printed beside its limit as the last lines on standard error, and the
last line on standard output is one JSON object.

It refuses to run anywhere but on a TPU with the chips the cell needs.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import lib  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = HERE / ".traces"
CALIBRATION_CALLS = 3
# A batch cell's profiler covers the jobs of its first seconds: a Gibbs
# job alone leaves some 8,000 device events in the trace.
TRACE_SECONDS = 5.0


class NoChip(RuntimeError):
    """The machine lacks the platform or chips the cell needs."""


def load_cell(name: str) -> types.SimpleNamespace:
    """The cell's entry and every file it is made of, found by name."""
    bench = lib.load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    traffic = lib.load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return types.SimpleNamespace(
        bench=bench, cell=cell,
        cfg=lib.load_json(HERE / "configs" / f"{cell['config']}.json"),
        cfgmod=lib.load_module(HERE / "configs" / f"{cell['config']}.py"),
        traffic=traffic,
        kind=lib.load_module(HERE / "kinds" / f"{traffic['kind']}.py"),
        limits=lib.load_json(HERE / "limits" / f"{name}.json"),
    )


def metrics_of(bench: dict, section: str, cell: str) -> list:
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]


def check_devices(chips: int, allow_cpu: bool):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU, but JAX found platform {platform!r} "
                     f"({len(devices)} device(s)); nothing was run")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX found "
                     f"{len(devices)}; nothing was run")
    return devices[:chips]


class TracedWindow:
    """The profiler's part of the window: ``begin`` opens the ``window``
    annotation, ``end`` closes it and stops the profiler (once).  A
    kind calls both; off a traced run they do nothing."""

    def __init__(self, on: bool):
        self.on = on
        self.anchor = None          # perf_counter when the window opened
        self._note = None

    def begin(self) -> None:
        if self.on and self._note is None:
            import jax

            self.anchor = time.perf_counter()
            self._note = jax.profiler.TraceAnnotation("window")
            self._note.__enter__()

    def end(self) -> None:
        if self.on and self._note is not None:
            import jax

            self._note.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False

    @property
    def open(self) -> bool:
        return self.on and self._note is not None


class CompileCounter:
    """Counts compilations, and programs read back from the persistent
    cache, through ``jax.monitoring``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name in self.EVENTS:
            self.count += 1


def use_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, through the program's own entry, which honours the
    variable set here; every program is written to it."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def calibrate_rate(trace_summary) -> float | None:
    import calibrate

    sec = sum(s for n, s in trace_summary.ops.items()
              if calibrate.KERNEL_NAME in n)
    return CALIBRATION_CALLS * calibrate.BLOCKS / sec if sec > 0 else None


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def log(msg: str) -> None:
    print(f"[run_cell] {msg}", file=sys.stderr, flush=True)


def run(name: str, seed: int, seconds: float, trace: bool, *,
        allow_cpu: bool = False, overrides: dict | None = None) -> dict:
    """One run of cell ``name``; returns the result line's object.

    ``allow_cpu`` and ``overrides`` (merged over the traffic and the
    configuration) exist for the rehearsal tests only; the command line
    sets neither.  Off the chip a run reports no metric: its times are
    not the device's."""
    c = load_cell(name)
    for key, val in (overrides or {}).get("traffic", {}).items():
        c.traffic[key] = val
    for key, val in (overrides or {}).get("config", {}).items():
        c.cfg[key] = val
    devices = check_devices(int(c.cell["chips"]), allow_cpu)
    import jax

    use_cache()
    compiles = CompileCounter()
    r = types.SimpleNamespace(
        name=name, seed=int(seed), seconds=float(seconds), trace=trace,
        cfg=c.cfg, cfgmod=c.cfgmod, traffic=c.traffic, devices=devices,
        annotate=jax.profiler.TraceAnnotation, log=log,
        traced=TracedWindow(trace), trace_seconds=TRACE_SECONDS,
    )
    state = c.kind.setup(r)
    if trace:
        import calibrate
        import trace as trace_mod

        from repro import telemetry

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        key = jax.numpy.asarray([seed & 0xFFFFFFFF, 7], jax.numpy.uint32)
        calibrate.threefry_calibration(key).block_until_ready()
        telemetry.enable()
        telemetry.TRACER.reset()
        epoch = time.perf_counter() - telemetry.clock()
        jax.profiler.start_trace(str(TRACE_DIR))
        with jax.profiler.TraceAnnotation("calibrate"):
            for _ in range(CALIBRATION_CALLS):
                calibrate.threefry_calibration(key).block_until_ready()
    setup_s = time.perf_counter() - T_START
    before = compiles.count
    res = c.kind.window(r, state)
    if trace:
        r.traced.end()
        r.spans = telemetry.TRACER.events()
        telemetry.disable()
        spans = [(e.name, epoch + e.ts_us * 1e-6,
                  epoch + (e.ts_us + e.dur_us) * 1e-6)
                 for e in r.spans if e.kind == "span"]
    in_window = compiles.count - before
    log(f"compilations inside the window: {in_window}")
    peak = memory_peak(devices)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    out = {"correct": None, "attempted": res.attempted, "failed": res.failed}
    metrics = {}
    on_chip = devices[0].platform == "tpu"
    if trace and on_chip:
        path = trace_mod.find_xplane(str(TRACE_DIR))
        sums = trace_mod.reduce(path, windows=("calibrate", "window"),
                                host_spans=spans,
                                anchor_s=r.traced.anchor)
        r.trace = sums["window"]
        r.calib_blocks_per_s = calibrate_rate(sums["calibrate"])
        r.peak = lib.load_json(HERE / "peaks.json").get(devices[0].device_kind)
        if r.peak is None:
            raise KeyError(f"no peaks for device kind "
                           f"{devices[0].device_kind!r} in peaks.json")
        r.result = res
        log(f"calibration: {r.calib_blocks_per_s} Threefry blocks/s "
            f"({calibrate.KERNEL_NAME})")
        for m in metrics_of(c.bench, "per_layer", name):
            reader = lib.load_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(r)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = r.trace.busy_s
        device["window_s"] = r.trace.window_s
        out["breakdown"] = trace_mod.breakdown(r.trace)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    elif on_chip:
        for m in metrics_of(c.bench, "end_to_end", name):
            value = setup_s if m["name"] == "setup_s" else res.e2e.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    c.kind.release(state)
    numbers = c.kind.check(r, res)
    checks = {}
    for key, value in numbers.items():
        limit = c.limits[key]
        checks[key] = {"value": value, "limit": limit}
    out["correct"] = bool(checks) and all(
        v["value"] <= v["limit"] for v in checks.values()
    )
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    for key, v in checks.items():
        log(f"check {key}: {v['value']} (limit {v['limit']})")
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True, help="cell name")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(str(e))
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
