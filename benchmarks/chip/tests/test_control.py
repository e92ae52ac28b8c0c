"""``correct`` comes out false where it has to.

* The control, the reference computed in bfloat16 put in the program's
  place, fails each configuration's limits.
* With the timed path broken underneath (``MHEngine.run`` patched), a
  whole run of every cell, chip check skipped, reads ``correct`` false
  for each fault the cell can have: a step that returns its state
  unchanged; half of the batch (lattices, or chains) left out; an
  answer altered where it is produced (one lattice's final state, or
  one kept row of every chain).  One chip: no exchange to leave out.

Sizes are small enough for a CPU test run; the readings at the cells'
own sizes on the chip are in PERF.md.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import lib
import run_cell
import stream
from test_rehearsal import SEED, TINY

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
LIMITS = Path(__file__).resolve().parents[1] / "limits"

CONTROL = {
    "ising-1024.batch.fused": ({"height": 32, "width": 32},
                              {"batch": 8, "n_steps": 256, "collect": "last"}),
    "gmm-v256.batch.fused": ({"chains": 1024},
                             {"n_steps": 256, "collect": "all"}),
    "gmm-v256.batch.cim": ({"chains": 1024},
                           {"n_steps": 256, "collect": "all"}),
}


@pytest.mark.parametrize("cell", sorted(CONTROL))
def test_control_fails_the_limits(cell):
    config, randomness = cell.split(".")[0], cell.split(".")[-1]
    cfg = {**lib.load_json(CONFIGS / f"{config}.json"), **CONTROL[cell][0]}
    mod = lib.load_module(CONFIGS / f"{config}.py")
    limits = lib.load_json(LIMITS / f"{cell}.json")
    base = stream.key_from_seed(SEED)
    case = dict(build_key=jax.random.fold_in(base, 0),
                run_key=jax.random.fold_in(base, 1), randomness=randomness,
                **CONTROL[cell][1])
    sound = mod.compare(cfg, case, mod.reference(cfg, case))
    assert all(sound[k] <= limits[k] for k in sound), sound
    control = mod.compare(cfg, case, mod.reference(cfg, case, "bfloat16"))
    assert any(control[k] > limits[k] for k in control), control


def _broken(fault):
    from repro.samplers import engine

    run = engine.MHEngine.run

    def broken(self, key, target, n_steps, init_words, **kw):
        res = run(self, key, target, n_steps, init_words, **kw)
        init = jnp.asarray(init_words).astype(jnp.uint32)
        samples, final = res.samples, res.final_words
        if fault == "unchanged":
            samples = jnp.broadcast_to(init, samples.shape)
            final = init
        elif fault == "half_batch":
            # lattices (B, H, W) or chains (1, C): the second half keeps
            # its start
            axis = 0 if init.ndim == 3 else init.ndim - 1
            n = init.shape[axis]
            keep = jnp.arange(n) < n // 2
            keep = keep.reshape([-1 if a == axis else 1
                                 for a in range(init.ndim)])
            samples = jnp.where(keep, samples, init)
            final = jnp.where(keep, final, init)
        elif samples.shape[0]:      # altered: one kept row, every chain
            samples = samples.at[samples.shape[0] // 2].set(
                samples[samples.shape[0] // 2] ^ jnp.uint32(1))
        else:                       # altered: one lattice's final state
            final = final.at[0].set(final[0] ^ jnp.uint32(1))
        return res._replace(samples=samples, final_words=final)

    return broken


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("cell", sorted(TINY))
def test_broken_path_reads_incorrect(cell, fault, monkeypatch):
    from repro.samplers import engine

    monkeypatch.setattr(engine.MHEngine, "run", _broken(fault))
    out = run_cell.run(cell, SEED + 1, 1.0, False, allow_cpu=True,
                       overrides=TINY[cell])
    assert out["correct"] is False, out["checks"]
