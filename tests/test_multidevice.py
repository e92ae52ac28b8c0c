"""Real N-device meshes (launch/mesh.py): 4-device sharded ==
unsharded bit-parity, sharded resume, and the streaming-diagnostic
shard merge.

Device-count tests run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI
multidevice job sets the same env process-wide); the main pytest
process keeps 1 CPU device.  The child env must SET
``JAX_PLATFORMS=cpu`` explicitly — unsetting it makes jax probe for
accelerator plugins, which stalls for minutes on CI containers.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.diagnostics import StreamingChainStats
from repro.launch.mesh import make_chains_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_forced(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


class TestMeshBuilder:
    def test_single_device_returns_none(self):
        # the main pytest process has 1 CPU device: no mesh to build
        if jax.device_count() == 1:
            assert make_chains_mesh(4) is None
        assert make_chains_mesh(1) is None

    def test_four_device_mesh_spans_devices(self):
        out = _run_forced("""
        import jax
        from repro.launch.mesh import make_chains_mesh

        assert jax.device_count() == 4, jax.devices()
        mesh = make_chains_mesh(4)
        assert mesh is not None
        assert mesh.axis_names == ("data",)
        assert mesh.devices.size == 4
        print("MESH-OK")
        """)
        assert "MESH-OK" in out


class TestShardedParity:
    def test_sharded_equals_unsharded_four_devices(self):
        """RunPlan(mesh=...) on 4 forced host devices reproduces the
        unsharded stream bit-for-bit, mh and gibbs."""
        out = _run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import samplers
        from repro.launch.mesh import make_chains_mesh
        from repro.workloads.ising import IsingModel

        assert jax.device_count() == 4, jax.devices()
        mesh = make_chains_mesh(4)
        key = jax.random.PRNGKey(7)

        table = jax.random.normal(jax.random.PRNGKey(0), (2, 64), jnp.float32)
        target = samplers.TableTarget(table)
        init = jnp.broadcast_to(
            jnp.argmax(table, -1).astype(jnp.uint32)[:, None], (2, 8)
        )
        cinit = jnp.broadcast_to(init, (4, *init.shape))
        eng = samplers.MHEngine(samplers.EngineConfig(
            num_chains=4, execution="scan", chunk_steps=8))
        plan = samplers.RunPlan(
            target=target, n_steps=16, init_words=cinit, key=key)
        a = eng.submit(plan.replace(mesh=mesh)).result
        b = eng.submit(plan).result
        np.testing.assert_array_equal(
            np.asarray(a.samples), np.asarray(b.samples))
        np.testing.assert_array_equal(
            np.asarray(a.accept_count), np.asarray(b.accept_count))

        model = IsingModel(height=6, width=6)
        ginit = model.random_init(jax.random.PRNGKey(1), 2)
        gcinit = jnp.broadcast_to(ginit, (4, *ginit.shape))
        geng = samplers.MHEngine(samplers.EngineConfig(
            update="gibbs", num_chains=4, chunk_steps=8))
        gplan = samplers.RunPlan(
            target=model, n_steps=12, init_words=gcinit, key=key)
        a = geng.submit(gplan.replace(mesh=mesh)).result
        b = geng.submit(gplan).result
        np.testing.assert_array_equal(
            np.asarray(a.samples), np.asarray(b.samples))
        # collect="last": the body's empty sample block is a constant
        a = geng.submit(gplan.replace(mesh=mesh, collect="last")).result
        b = geng.submit(gplan.replace(collect="last")).result
        assert a.samples.shape[1] == 0
        np.testing.assert_array_equal(
            np.asarray(a.final_words), np.asarray(b.final_words))
        print("SHARDED-4-OK")
        """)
        assert "SHARDED-4-OK" in out

    def test_sharded_resume_bit_exact(self):
        """A checkpointed run killed mid-flight resumes bit-exactly on a
        4-device mesh (and matches the unsharded unsegmented run)."""
        out = _run_forced("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro import samplers
        from repro.checkpoint import run_resumable
        from repro.launch.mesh import make_chains_mesh

        assert jax.device_count() == 4, jax.devices()
        mesh = make_chains_mesh(4)
        key = jax.random.PRNGKey(3)
        table = jax.random.normal(jax.random.PRNGKey(0), (2, 64), jnp.float32)
        target = samplers.TableTarget(table)
        init = jnp.broadcast_to(
            jnp.argmax(table, -1).astype(jnp.uint32)[:, None], (2, 8)
        )
        cinit = jnp.broadcast_to(init, (4, *init.shape))
        eng = samplers.MHEngine(samplers.EngineConfig(
            num_chains=4, execution="scan", chunk_steps=8))
        plan = samplers.RunPlan(
            target=target, n_steps=24, init_words=cinit, key=key, mesh=mesh)
        ref = eng.submit(plan.replace(mesh=None)).result

        with tempfile.TemporaryDirectory() as d:
            class Die(RuntimeError):
                pass

            def die(done, total, handle):
                if done >= 8:
                    raise Die

            try:
                run_resumable(eng, plan, directory=d, every=8, on_segment=die)
                raise AssertionError("expected the preemption")
            except Die:
                pass
            handle = run_resumable(eng, plan, directory=d, every=8)
        np.testing.assert_array_equal(
            np.asarray(handle.samples), np.asarray(ref.samples))
        np.testing.assert_array_equal(
            np.asarray(handle.final_words), np.asarray(ref.final_words))
        np.testing.assert_array_equal(
            np.asarray(handle.acceptance_rate),
            np.asarray(ref.acceptance_rate))
        print("RESUME-4-OK")
        """)
        assert "RESUME-4-OK" in out


class TestShardedServing:
    def test_slot_sharded_serving_equals_unsharded(self):
        """Scheduler(mesh=...) on 4 forced host devices: a mixed
        ising+gmm burst with slot-sharded class programs reproduces the
        unsharded burst bit-for-bit (slots never communicate, so the
        shard_map wrap is collective-free)."""
        out = _run_forced("""
        import jax, numpy as np
        from repro.launch.mesh import make_chains_mesh
        from repro.serving import Scheduler, ServeRequest

        assert jax.device_count() == 4, jax.devices()
        mesh = make_chains_mesh(4)
        assert mesh is not None

        def reqs():
            return [
                ServeRequest(rid=0, workload="gmm", n_steps=16, seed=1,
                             collect="all"),
                ServeRequest(rid=1, workload="ising", n_steps=12, seed=2,
                             collect="all"),
                ServeRequest(rid=2, workload="gmm", n_steps=24, seed=3,
                             collect="last"),
                ServeRequest(rid=3, workload="ising", n_steps=8, seed=4,
                             collect="last"),
            ]

        done_m = Scheduler(
            n_slots=4, smoke=True, chunk_steps=8, mesh=mesh
        ).serve(reqs())
        done_u = Scheduler(
            n_slots=4, smoke=True, chunk_steps=8
        ).serve(reqs())
        bm = {r.rid: r for r in done_m}
        bu = {r.rid: r for r in done_u}
        for rid in range(4):
            np.testing.assert_array_equal(
                bm[rid].samples, bu[rid].samples)
            np.testing.assert_array_equal(
                bm[rid].final_words, bu[rid].final_words)
            np.testing.assert_array_equal(
                bm[rid].accept_count, bu[rid].accept_count)
        print("SERVE-SHARD-OK")
        """)
        assert "SERVE-SHARD-OK" in out


class TestStreamingMerge:
    def _feed(self, stats, block, chunk=16):
        for s in range(0, block.shape[0], chunk):
            stats.update(block[s : s + chunk])

    def test_merge_equals_joint_accumulator(self):
        """Per-shard accumulators merged across the chain axis must equal
        one accumulator fed the full (T, C) block — exact, because chains
        never communicate."""
        rng = np.random.default_rng(0)
        block = rng.normal(size=(96, 6)).astype(np.float64)
        joint = StreamingChainStats(num_chains=6, total_steps=96)
        self._feed(joint, block)
        shards = []
        for lo, hi in ((0, 2), (2, 4), (4, 6)):
            s = StreamingChainStats(num_chains=hi - lo, total_steps=96)
            self._feed(s, block[:, lo:hi])
            shards.append(s)
        merged = StreamingChainStats.merge_shards(shards)
        a, b = merged.summarize(), joint.summarize()
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_allclose(a[k], b[k], rtol=0, atol=0)

    def test_merge_refuses_mismatched_shapes(self):
        a = StreamingChainStats(num_chains=2, total_steps=64)
        b = StreamingChainStats(num_chains=2, total_steps=32)
        with pytest.raises(ValueError):
            a.merge(b)
