"""The work and byte counts of each configuration, by hand."""

from pathlib import Path

import lib

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _config(name):
    return (lib.load_json(CONFIGS / f"{name}.json"),
            lib.load_module(CONFIGS / f"{name}.py"))


def test_ising_work():
    cfg, mod = _config("ising-1024")
    job = {"batch": 64, "n_steps": 1024, "collect": "last"}
    sites = 64 * 1024 * 1024
    assert mod.job_updates(cfg, job) == sites * 1024
    # one uniform per active-colour site and one step key per half-sweep
    assert mod.job_blocks(cfg, job, "fused") == sites // 2 * 1024 + 1024
    assert mod.job_blocks(cfg, job, "cim") is None
    # initial words in; final words, flip counts, log-probs out
    assert mod.job_bytes(cfg, job) == 4 * 4 * sites


def test_gmm_work():
    cfg, mod = _config("gmm-v256")
    job = {"n_steps": 2048, "collect": "all"}
    c = cfg["chains"]
    assert mod.job_updates(cfg, job) == c * 2048
    # step key + 8 flip planes + 1 uniform per chain-step
    assert mod.job_blocks(cfg, job, "fused") == c * 2048 * 10
    assert mod.job_blocks(cfg, job, "cim") is None
    # table + (init, final, accept, logp) + 2048 kept rows
    assert mod.job_bytes(cfg, job) == 4 * 256 + 4 * c * (4 + 2048)
    assert mod.job_bytes(cfg, {"n_steps": 2048, "collect": "last"}) == (
        4 * 256 + 16 * c)


def test_gmm_table_is_the_mixture():
    """The reference table is normalised over the grid up to the cell
    width and peaks at the mixture's heaviest modes."""
    import numpy as np

    cfg, mod = _config("gmm-v256")
    t = mod.table(cfg).astype(np.float64)
    width = (cfg["hi"] - cfg["lo"]) / 2 ** cfg["nbits"]
    assert abs(np.exp(t).sum() * width - 1.0) < 1e-3
    x = cfg["lo"] + (np.argmax(t) + 0.5) * width
    assert min(abs(x - 2.0), abs(x + 2.0)) < 0.2


def test_poisson_and_steps_fixed_per_seed():
    """Every seed offers the same arrivals and budgets, in another order."""
    import numpy as np

    a = lib.poisson_gaps(50, 10.0, lib.seed_rng(1, 2))
    b = lib.poisson_gaps(50, 10.0, lib.seed_rng(2, 2))
    assert not np.array_equal(a, b)
    assert np.allclose(np.sort(a), np.sort(b))
    s = lib.log_uniform_steps(200, 128, 4096, 32, lib.seed_rng(3, 2))
    assert s.min() >= 128 and s.max() <= 4096 and not (s % 32).any()


def test_percentile_matches_numpy_and_counts_missing():
    import math

    import numpy as np

    xs = [0.3, 1.0, 0.2, 5.0, 0.7]
    for q in (50, 95):
        assert lib.percentile(xs, q) == np.percentile(xs, q)
    assert lib.percentile(xs + [math.inf] * 5, 95) == math.inf


def test_calibration_kernel_does_every_block():
    """The calibration kernel computes each of its blocks (interpret
    mode): its XOR fold equals the plain fold over all counters, so the
    rate it gives is not inflated by skipped work."""
    import jax.numpy as jnp
    import numpy as np

    import calibrate

    key = jnp.asarray([0x12345678, 0x9ABCDEF0], jnp.uint32)
    got = calibrate.threefry_calibration(key, interpret=True, grid=2, iters=3)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(calibrate.reference_fold(key, 2, 3)))


def test_stream_cipher_is_jax_threefry():
    """The benchmark's cipher is Threefry-2x32-20 as ``jax.random``'s."""
    import jax.numpy as jnp
    import numpy as np
    from jax._src.prng import threefry_2x32

    import stream

    key = jnp.asarray([7, 11], jnp.uint32)
    x = jnp.arange(8, dtype=jnp.uint32)
    want = np.asarray(threefry_2x32(key, jnp.concatenate([x, x + 8])))
    got = np.concatenate([np.asarray(w) for w in
                          stream.threefry2x32(key[0], key[1], x, x + 8)])
    np.testing.assert_array_equal(got, want)
