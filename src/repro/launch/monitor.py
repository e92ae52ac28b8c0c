"""Tail, summarize, or validate a telemetry trace file.

The read-side companion of ``--trace`` (DESIGN.md §Telemetry): point it
at a JSONL trace emitted by ``launch/sample``, ``launch/serve_engine``
or ``benchmarks/run`` and get a per-span-name aggregation (count, total
/ mean / max duration, and share of traced time by self time, so a
nested span is not counted twice) plus the instant/log events.
``--check`` validates every line against the trace event
schema and exits non-zero on the first malformed file — the CI
telemetry smoke runs exactly this.  ``--follow`` tails a live file,
printing events as a run appends them.

Usage:
  PYTHONPATH=src python -m repro.launch.sample --workload ising --smoke \
      --trace out.trace.jsonl
  PYTHONPATH=src python -m repro.launch.monitor out.trace.jsonl
  PYTHONPATH=src python -m repro.launch.monitor --check out.trace.jsonl
  PYTHONPATH=src python -m repro.launch.monitor --follow live.trace.jsonl
"""

from __future__ import annotations

import argparse
import json
import time

from repro import telemetry


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="repro.launch.monitor",
        description="Tail/summarize/validate a telemetry JSONL trace.",
    )
    p.add_argument("trace", help="JSONL trace file (--trace output)")
    p.add_argument(
        "--check", action="store_true",
        help="validate against the event schema; exit 1 on any problem",
    )
    p.add_argument(
        "--follow", action="store_true",
        help="tail the file, printing events as they are appended",
    )
    p.add_argument(
        "--top", type=int, default=20,
        help="span names shown in the summary (by total duration)",
    )
    return p


def read_events(path: str) -> tuple[dict | None, list[dict]]:
    """(header, events) from a JSONL trace; malformed lines are skipped
    (use --check for strict validation)."""
    header = None
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if obj.get("kind") == "trace_meta":
                header = obj
            else:
                events.append(obj)
    return header, events


def _self_times(spans: list[dict]) -> tuple[list[float], float]:
    """Each span's self time in µs: its duration less its children's,
    the spans one level deeper on the same ``tid`` that start inside it.
    Also the time of the top-level spans, those with no parent among
    ``spans``; the self times sum to it."""

    keys = [
        (ev.get("tid", 0), float(ev.get("ts_us", 0.0)), ev.get("depth", 0))
        for ev in spans
    ]
    durs = [float(ev.get("dur_us", 0.0)) for ev in spans]
    own = list(durs)
    top = 0.0
    stack: list[int] = []      # open spans of one tid, outermost first
    for i in sorted(range(len(spans)), key=keys.__getitem__):
        tid, t0, depth = keys[i]
        while stack:
            p_tid, p_t0, p_depth = keys[stack[-1]]
            if p_tid == tid and p_depth < depth and t0 < p_t0 + durs[stack[-1]]:
                break
            stack.pop()
        if stack and keys[stack[-1]][2] == depth - 1:
            own[stack[-1]] -= durs[i]
        else:
            top += durs[i]
        stack.append(i)
    return own, top


def summarize_events(events: list[dict], top: int = 20) -> list[dict]:
    """Per-span-name aggregate rows, sorted by total duration.

    ``total_ms`` is each name's summed duration; ``share`` is its self
    time (children's time taken out, so a nested span is not counted
    twice) over the top-level time, so the shares add up to 1."""
    spans = [ev for ev in events if ev.get("kind") == "span"]
    own, top_us = _self_times(spans)
    agg: dict[str, dict] = {}
    for ev, self_us in zip(spans, own):
        row = agg.setdefault(
            ev["name"],
            {"count": 0, "total_us": 0.0, "self_us": 0.0, "max_us": 0.0},
        )
        dur = float(ev.get("dur_us", 0.0))
        row["count"] += 1
        row["total_us"] += dur
        row["self_us"] += self_us
        row["max_us"] = max(row["max_us"], dur)
    total = top_us or 1.0
    rows = []
    for name, r in sorted(
        agg.items(), key=lambda kv: -kv[1]["total_us"]
    )[: max(1, top)]:
        rows.append(
            {
                "span": name,
                "count": r["count"],
                "total_ms": round(r["total_us"] / 1e3, 3),
                "mean_us": round(r["total_us"] / r["count"], 1),
                "max_us": round(r["max_us"], 1),
                "share": round(r["self_us"] / total, 3),
            }
        )
    return rows


def _print_summary(path: str, top: int) -> int:
    header, events = read_events(path)
    spans = [e for e in events if e.get("kind") == "span"]
    instants = [e for e in events if e.get("kind") == "instant"]
    print(
        f"[monitor] {path}: {len(spans)} spans, {len(instants)} instants"
        + (
            f", {header.get('dropped', 0)} dropped (ring overflow)"
            if header
            else ", no header (partial file?)"
        )
    )
    for row in summarize_events(events, top=top):
        print("  " + "  ".join(f"{k}={v}" for k, v in row.items()))
    if instants:
        print("[monitor] last instants:")
        for ev in instants[-min(10, len(instants)):]:
            meta = ev.get("meta", {})
            print(
                f"  {ev['name']} @ {float(ev['ts_us']) / 1e6:.3f}s  "
                + "  ".join(f"{k}={v}" for k, v in meta.items())
            )
    return 0


def _check(path: str) -> int:
    problems = telemetry.validate_jsonl(path)
    if problems:
        print(f"[monitor] {path}: INVALID ({len(problems)} problems)")
        for msg in problems[:20]:
            print(f"  {msg}")
        return 1
    header, events = read_events(path)
    print(
        f"[monitor] {path}: valid trace (schema "
        f"{header.get('schema') if header else '?'}, {len(events)} events)"
    )
    return 0


def _follow(path: str) -> int:  # pragma: no cover - interactive loop
    with open(path) as f:
        while True:
            line = f.readline()
            if not line:
                time.sleep(0.2)
                continue
            line = line.strip()
            if line:
                print(line)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.check:
        return _check(args.trace)
    if args.follow:
        return _follow(args.trace)
    return _print_summary(args.trace, args.top)


if __name__ == "__main__":
    raise SystemExit(main())
