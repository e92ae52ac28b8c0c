"""Small pieces the harness, the kinds and the readers share.

Copied here on purpose, not imported from the program, so that a later
change to the program cannot move the yardstick:

* ``percentile`` is linear interpolation between closest ranks, the
  arithmetic of ``numpy.percentile``'s default that the serving tier's
  ``latency_summary`` uses;
* ``poisson_gaps`` keeps the seeded exponential gaps of
  ``launch/serve_engine.poisson_requests``, but takes them as fixed
  quantiles of the exponential distribution in an order the generator
  draws, so the work offered is the same for every draw.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path; its name may hold dots and dashes."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks; +inf
    entries (requests that never finished) sort last."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == math.inf:
        return math.inf if pos > lo or xs[lo] == math.inf else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one named use of the run's seed."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  int(stream)])


def poisson_gaps(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process of ``rate`` per
    second: the exponential quantiles at (i + 0.5) / n, in an order the
    generator draws."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return rng.permutation(gaps)


def log_uniform_steps(n: int, lo: int, hi: int, multiple: int,
                      rng: np.random.Generator) -> np.ndarray:
    """``n`` step budgets log-uniform over [lo, hi] at the quantiles
    (i + 0.5) / n, rounded to ``multiple``, in an order the generator
    draws."""
    q = (np.arange(n) + 0.5) / n
    steps = np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
    steps = np.clip(np.round(steps / multiple) * multiple, lo, hi)
    return rng.permutation(steps.astype(np.int64))
