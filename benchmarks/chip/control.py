"""Readings that set each cell's limits, on the chip, in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> --control-seeds <m> [--first-seed <k>]

* Lower readings: ``n`` runs of the cell (``run_cell.run``, a short
  window at the cell's own load) on seeds k, k+1, ...: every number the
  run compares, as the sound program gives it.
* Upper readings: the control, the reference computed in bfloat16 put
  in the program's place, on ``m`` further seeds: for the cases such a
  run checks, the control's outputs go through the same comparison.

Prints one line per run and, last, a JSON object with every reading and,
per number, the largest lower and the smallest upper reading.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import types

import run_cell


def control_numbers(name: str, seed: int, seconds: float) -> dict:
    c = run_cell.load_cell(name)
    r = types.SimpleNamespace(seed=seed, seconds=seconds, cfg=c.cfg,
                              cfgmod=c.cfgmod, traffic=c.traffic)
    total: dict = {}
    for case in c.kind.control_cases(r):
        out = c.cfgmod.reference(c.cfg, case, precision="bfloat16")
        for key, val in c.cfgmod.compare(c.cfg, case, out).items():
            total[key] = total.get(key, 0) + val
    return total


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=3_000_000_000)
    args = p.parse_args(argv)
    lower, upper = [], []
    seed = args.first_seed
    for _ in range(args.seeds):
        out = run_cell.run(args.workload, seed, args.seconds, False)
        nums = {k: v["value"] for k, v in out["checks"].items()}
        lower.append({"seed": seed, "attempted": out["attempted"], **nums})
        print(f"program seed {seed}: {nums}", flush=True)
        seed += 1
    for _ in range(args.control_seeds):
        nums = control_numbers(args.workload, seed, args.seconds)
        upper.append({"seed": seed, **nums})
        print(f"control seed {seed}: {nums}", flush=True)
        seed += 1
    keys = sorted({k for row in lower + upper for k in row
                   if k not in ("seed", "attempted")})
    print(json.dumps({
        "workload": args.workload, "lower": lower, "upper": upper,
        "max_lower": {k: max(r.get(k, 0) for r in lower) for k in keys},
        "min_upper": {k: min(r.get(k, 0) for r in upper) for k in keys},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
