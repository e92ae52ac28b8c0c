"""Chains axis (DESIGN.md §Chains-axis): chains==solo bit-parity, chunk
invariance with C>1, workload wiring, and sharded==unsharded equality.

The contract under test: per-chain randomness (and per-chain workload
inits) are counter-derived from ``(chain_id, absolute_step)``, so chain c
of a C-chain run is bit-identical to a solo run with ``chain_id=c`` —
for both randomness backends, both update rules, and both executors —
and sharding the chain axis over a device mesh changes nothing.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import samplers, workloads
from repro.launch import sample as sample_cli
from repro.workloads.ising import IsingModel

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _mh_target(b=2, v=64, chains=8, seed=0):
    table = jax.random.normal(jax.random.PRNGKey(seed), (b, v), jnp.float32)
    init = jnp.broadcast_to(
        jnp.argmax(table, -1).astype(jnp.uint32)[:, None], (b, chains)
    )
    return samplers.TableTarget(table), init


def _gibbs_target(b=2, h=6, w=6, seed=0):
    model = IsingModel(height=h, width=w, beta=0.35)
    return model, model.random_init(jax.random.PRNGKey(seed), b)


def _engine(**kw):
    return samplers.MHEngine(samplers.EngineConfig(**kw))


def _bcast(init, num_chains):
    """Explicit chain broadcast — the engine requires the leading axis."""
    return jnp.broadcast_to(init, (num_chains, *init.shape))


class TestChainsSoloParity:
    @pytest.mark.parametrize("randomness", ["host", "cim"])
    @pytest.mark.parametrize("execution", ["scan", "pallas"])
    @pytest.mark.parametrize("update", ["mh", "gibbs"])
    def test_chain_of_multi_run_equals_solo(
        self, randomness, execution, update
    ):
        """The ISSUE-3 acceptance matrix: every {randomness} x {executor}
        x {update rule} cell satisfies chains==solo bit-parity."""
        if update == "mh":
            target, init = _mh_target()
        else:
            target, init = _gibbs_target()
        key = jax.random.PRNGKey(7)
        n_steps, num_chains = 22, 3
        multi = _engine(
            update=update, randomness=randomness, execution=execution,
            num_chains=num_chains, chunk_steps=8,
        ).run(key, target, n_steps, _bcast(init, num_chains))
        solo_engine = _engine(
            update=update, randomness=randomness, execution=execution,
            chunk_steps=8,
        )
        for c in range(num_chains):
            solo = solo_engine.run(key, target, n_steps, init, chain_id=c)
            np.testing.assert_array_equal(
                np.asarray(multi.samples[c]), np.asarray(solo.samples)
            )
            np.testing.assert_array_equal(
                np.asarray(multi.accept_count[c]),
                np.asarray(solo.accept_count),
            )
            np.testing.assert_array_equal(
                np.asarray(multi.final_logp[c]), np.asarray(solo.final_logp)
            )

    @pytest.mark.parametrize("update", ["mh", "gibbs"])
    def test_scan_and_pallas_multi_chain_bit_identical(self, update):
        """Executor parity survives the chains axis (the pallas side runs
        a genuinely batched grid, not a python loop over chains)."""
        target, init = _mh_target() if update == "mh" else _gibbs_target()
        key = jax.random.PRNGKey(3)
        runs = {}
        for execution in ("scan", "pallas"):
            runs[execution] = _engine(
                update=update, execution=execution, num_chains=4,
                chunk_steps=8,
            ).run(key, target, 20, _bcast(init, 4))
        np.testing.assert_array_equal(
            np.asarray(runs["scan"].samples), np.asarray(runs["pallas"].samples)
        )
        np.testing.assert_array_equal(
            np.asarray(runs["scan"].accept_count),
            np.asarray(runs["pallas"].accept_count),
        )

    @pytest.mark.parametrize("update", ["mh", "gibbs"])
    def test_chunked_vs_monolithic_with_chains(self, update):
        """Chunk invariance must hold per chain: randomness for
        (chain, step) depends only on (key, chain_id, t)."""
        target, init = _mh_target() if update == "mh" else _gibbs_target()
        key = jax.random.PRNGKey(11)
        r_chunked = _engine(update=update, num_chains=4, chunk_steps=7).run(
            key, target, 30, _bcast(init, 4)
        )
        r_mono = _engine(update=update, num_chains=4, chunk_steps=1000).run(
            key, target, 30, _bcast(init, 4)
        )
        np.testing.assert_array_equal(
            np.asarray(r_chunked.samples), np.asarray(r_mono.samples)
        )
        np.testing.assert_array_equal(
            np.asarray(r_chunked.accept_count),
            np.asarray(r_mono.accept_count),
        )

    def test_per_chain_init_respected(self):
        """A (num_chains, ...) init seeds each chain separately; an
        init without the leading chain axis is rejected, never guessed
        (a solo init whose first dim equals num_chains would otherwise
        be silently misread as per-chain)."""
        target, init = _mh_target(chains=4)
        per_chain = jnp.stack([init, init + 1, init + 2])
        key = jax.random.PRNGKey(0)
        multi = _engine(num_chains=3).run(key, target, 8, per_chain)
        for c in range(3):
            solo = _engine().run(key, target, 8, per_chain[c], chain_id=c)
            np.testing.assert_array_equal(
                np.asarray(multi.samples[c]), np.asarray(solo.samples)
            )
        with pytest.raises(ValueError, match="leading"):
            _engine(num_chains=3).run(key, target, 8, init)
        # pallas executors additionally pin the per-chain rank, so a
        # solo-shaped init whose first dim collides with num_chains is
        # caught rather than silently folded
        with pytest.raises(ValueError, match="num_chains, B, C"):
            _engine(num_chains=2, execution="pallas").run(
                key, target, 8, init
            )

    def test_chain_id_base_composes_multi_runs(self):
        """chain_id offsets a multi-chain run: two 4-chain runs with
        bases 0 and 4 are exactly the 8-chain run, stream for stream."""
        target, init = _mh_target()
        key = jax.random.PRNGKey(5)
        full = _engine(num_chains=8).run(key, target, 10, _bcast(init, 8))
        eng4 = _engine(num_chains=4)
        lo = eng4.run(key, target, 10, _bcast(init, 4), chain_id=0)
        hi = eng4.run(key, target, 10, _bcast(init, 4), chain_id=4)
        np.testing.assert_array_equal(
            np.asarray(full.samples),
            np.concatenate([np.asarray(lo.samples), np.asarray(hi.samples)]),
        )

    def test_num_chains_validation(self):
        with pytest.raises(ValueError):
            samplers.EngineConfig(num_chains=0)


class TestWorkloadChains:
    @pytest.mark.parametrize("name", ["ising", "gmm"])
    def test_workload_chain0_equals_solo_build(self, name):
        """The CLI acceptance criterion: --num-chains C vs --num-chains 1
        agree on chain 0 bit-for-bit, inits included."""
        k_init, k_run = jax.random.split(jax.random.PRNGKey(0))
        multi = workloads.build(
            name, k_init, smoke=True, n_steps=16, backend="pallas",
            num_chains=4,
        )
        solo = workloads.build(
            name, k_init, smoke=True, n_steps=16, backend="pallas",
            num_chains=1,
        )
        np.testing.assert_array_equal(
            np.asarray(multi.init_words[0]), np.asarray(solo.init_words)
        )
        np.testing.assert_array_equal(
            np.asarray(multi.run(k_run).samples[0]),
            np.asarray(solo.run(k_run).samples),
        )

    def test_cli_num_chains_smoke(self, capsys):
        row = sample_cli.main(
            ["--workload", "ising", "--smoke", "--steps", "12",
             "--num-chains", "4", "--backend", "pallas"]
        )
        assert row["num_chains"] == 4
        assert "ess" in row and "split_rhat" in row
        # 4 chains x 2 smoke lattices contribute 8 diagnostic columns
        assert row["n_chains"] == 8
        assert "num_chains=4" in capsys.readouterr().out

    def test_multi_chain_diagnostics_stream_matches_batch(self):
        """WorkloadRun.diagnostics streams the (T, C·m) block in chunks;
        the result must equal the batch estimator over the same block."""
        from repro import diagnostics

        k_init, k_run = jax.random.split(jax.random.PRNGKey(1))
        wl = workloads.build(
            "gmm", k_init, smoke=True, n_steps=40, num_chains=3,
            backend="scan",
        )
        result = wl.run(k_run)
        streamed = wl.diagnostics(result)
        series = wl.series(result)[wl.burn_in:]
        batch = diagnostics.summarize(
            series, acceptance_rate=float(result.acceptance_rate)
        )
        assert streamed == batch


class TestShardedChains:
    def test_sharded_equals_unsharded_two_device_mesh(self):
        """shard_map over a mocked 2-device mesh: the chain axis shards,
        the sample streams do not change (subprocess — the main pytest
        process keeps 1 CPU device)."""
        code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro import samplers
        from repro.workloads.ising import IsingModel

        assert jax.device_count() == 2, jax.devices()
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))
        key = jax.random.PRNGKey(7)

        table = jax.random.normal(jax.random.PRNGKey(0), (2, 64), jnp.float32)
        target = samplers.TableTarget(table)
        init = jnp.broadcast_to(
            jnp.argmax(table, -1).astype(jnp.uint32)[:, None], (2, 8)
        )
        cinit = jnp.broadcast_to(init, (4, *init.shape))
        eng = samplers.MHEngine(samplers.EngineConfig(
            num_chains=4, execution="scan", chunk_steps=8))
        a = eng.run(key, target, 16, cinit, mesh=mesh)
        b = eng.run(key, target, 16, cinit)
        np.testing.assert_array_equal(
            np.asarray(a.samples), np.asarray(b.samples))

        model = IsingModel(height=6, width=6)
        ginit = model.random_init(jax.random.PRNGKey(1), 2)
        gcinit = jnp.broadcast_to(ginit, (4, *ginit.shape))
        geng = samplers.MHEngine(samplers.EngineConfig(
            update="gibbs", num_chains=4, chunk_steps=8))
        a = geng.run(key, model, 12, gcinit, mesh=mesh)
        b = geng.run(key, model, 12, gcinit)
        np.testing.assert_array_equal(
            np.asarray(a.samples), np.asarray(b.samples))

        # a chain count the mesh doesn't divide replicates (still correct)
        odd = samplers.MHEngine(samplers.EngineConfig(num_chains=3)).run(
            key, target, 8, jnp.broadcast_to(init, (3, *init.shape)),
            mesh=mesh)
        assert odd.samples.shape[0] == 3
        print("SHARDED-OK")
        """
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["PYTHONPATH"] = SRC
        # keep the child on the CPU platform explicitly: popping
        # JAX_PLATFORMS makes jax probe for accelerator plugins, which
        # stalls for minutes on CI-like containers
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(code)],
            capture_output=True, text=True, env=env, timeout=900,
        )
        assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
        assert "SHARDED-OK" in out.stdout
