"""A CPU rehearsal of the token-sampling decode cell and the four-chip
Ising cell at tiny sizes (as ``test_rehearsal.py``): the four-chip cell
on four host devices, in a process of its own."""

import json
import os
import subprocess
import sys
from pathlib import Path

import run_cell

HERE = Path(__file__).resolve().parent
SEED = 2**31 + 17
TOKENS = {"config": {"batch": 16, "vocab": 3001, "nbits": 12, "tables": 3},
          "traffic": {"execution": "pallas"}}


def test_token_cell_rehearses_correct():
    out = run_cell.run("tokens-v129280.decode.fused", SEED, 1.0, False,
                       allow_cpu=True, overrides=TOKENS)
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == {"tokens_off", "accepts_off", "logp_off"}
    assert out["attempted"] >= 1 and out["metrics"] == {}


def test_four_chip_cell_rehearses_on_four_host_devices():
    script = (
        "import json, run_cell\n"
        "ov = {'config': {'height': 16, 'width': 16}, 'traffic': "
        "{'builder': {'batch': 2, 'num_chains': 4}, 'n_steps': 64}}\n"
        f"out = run_cell.run('ising-1024.batch.fused.4chip', {SEED}, 1.0, "
        "False, allow_cpu=True, overrides=ov)\n"
        "print(json.dumps(out))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(HERE.parent), str(HERE.parents[2] / "src")]))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4
