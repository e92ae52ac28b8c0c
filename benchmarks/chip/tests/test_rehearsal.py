"""A CPU rehearsal of every cell at tiny sizes.

The harness's look for a chip is skipped (``allow_cpu``); everything
else runs as on the chip: set-up, the window, the reference check.  The
gmm cells run the MH kernel in Pallas interpret mode.  Off the chip a
run reports no metric, since its times are not the device's."""

import pytest

import run_cell

SEED = 2**31 + 17
TINY = {
    "ising-1024.batch.fused": {
        "config": {"height": 16, "width": 16},
        "traffic": {"builder": {"batch": 4}, "n_steps": 64}},
    "gmm-v256.batch.fused": {
        "config": {"chains": 256},
        "traffic": {"n_steps": 64, "execution": "pallas"}},
    "gmm-v256.batch.cim": {
        "config": {"chains": 256},
        "traffic": {"n_steps": 64, "execution": "pallas"}},
    "ising-1024.serve.poisson": {
        "config": {"height": 16, "width": 16},
        "traffic": {"builder": {"batch": 2}, "slots": 4, "rate": 20.0,
                    "steps_lo": 32, "steps_hi": 256}},
}


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_rehearses_correct_with_no_metric(cell):
    out = run_cell.run(cell, SEED, 1.0, False, allow_cpu=True,
                       overrides=TINY[cell])
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert all(v["value"] <= v["limit"] for v in out["checks"].values())


def test_refuses_without_a_chip():
    with pytest.raises(run_cell.NoChip):
        run_cell.run("ising-1024.batch.fused", SEED, 1.0, False)
