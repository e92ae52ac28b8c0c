"""Find a serve cell's knee on the chip: one sweep over fixed rates.

    python3 benchmarks/chip/knee.py --workload <serve cell> --seconds <s> \
        --rates 3 4 5 6 [--seed <n>]

One process sets the cell up once (the same warmed ``Scheduler``), then
serves the cell's traffic at each rate in turn for ``--seconds``, as a
run's window does.  Per rate it prints one JSON line: requests offered,
the rate at which they completed, the median and 95th percentile
latency, and the mean queue wait of the first and the last third of the
arrivals.  The knee is the highest rate whose wait does not grow from
the first third to the last; the cell's ``rate`` is set at about four
fifths of it, by hand.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys

import jax

import lib
import run_cell


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seed", type=int, default=3_900_000_000)
    args = p.parse_args(argv)
    c = run_cell.load_cell(args.workload)
    devices = run_cell.check_devices(int(c.cell["chips"]), False)
    run_cell.use_cache()
    r = run_cell.types.SimpleNamespace(
        name=args.workload, seed=args.seed, seconds=args.seconds,
        trace=False, cfg=c.cfg, cfgmod=c.cfgmod, traffic=c.traffic,
        devices=devices, annotate=jax.profiler.TraceAnnotation,
        log=run_cell.log, traced=run_cell.TracedWindow(False),
    )
    state = c.kind.setup(r)
    rows = []
    for rate in args.rates:
        r.traffic["rate"] = rate
        state.requests = c.kind.make_requests(r, lib.seed_rng(args.seed, 2))
        res = c.kind.window(r, state)
        waits = [q.wait_s for q in res.requests if q.wait_s is not None]
        third = max(1, len(waits) // 3)
        done = len(res.requests) - res.failed
        rows.append({
            "rate": rate, "n": len(res.requests),
            "done_per_s": done / (res.t_end - res.t_begin),
            "p50": res.e2e["p50_latency_s"], "p95": res.e2e["p95_latency_s"],
            "wait_first": statistics.mean(waits[:third]),
            "wait_last": statistics.mean(waits[-third:]),
        })
        print(json.dumps(rows[-1]), flush=True)
        state.sched.done.clear()    # each served request holds its lattices
    print("SWEEP " + json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
