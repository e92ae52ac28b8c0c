"""Multi-device distributed tests: run in subprocesses with fake devices
(the main pytest process keeps 1 CPU device)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n_devices: int = 8, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


class TestCompressedPodPsum:
    def test_int8_error_feedback_reduction(self):
        """Compressed pod-psum matches the exact mean within int8 rounding,
        and the error feedback makes the *accumulated* series exact."""
        out = run_with_devices(
            """
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P, AxisType
            from repro.distributed.compression import compressed_pmean

            mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                                 axis_types=(AxisType.Auto,)*3)
            rng = np.random.default_rng(0)
            g_pods = rng.normal(size=(2, 64)).astype(np.float32)

            def body(err_w):
                g_true = jnp.asarray(g_pods)  # (2, 64)
                def inner(e):
                    idx = jax.lax.axis_index('pod')
                    g = g_true[idx]  # pod-varying gradient
                    red, new_e = compressed_pmean({'w': g}, {'w': e}, 'pod')
                    return red['w'], new_e['w']
                return jax.shard_map(inner, mesh=mesh, in_specs=P(),
                                     out_specs=(P(), P()), axis_names={'pod'},
                                     check_vma=False)(err_w)

            err = jnp.zeros(64, jnp.float32)
            true_mean = g_pods.mean(axis=0)
            acc_red = np.zeros(64)
            scale = np.abs(g_pods).max() / 127.0
            for it in range(4):
                red, err = jax.jit(body)(err)
                red = np.asarray(red)
                acc_red += red
                # single-step error bounded by int8 quantisation
                assert np.abs(red - true_mean).max() <= scale * 1.01, it
            # error feedback: accumulated mean converges tighter than 1 step
            drift = np.abs(acc_red / 4 - true_mean).max()
            assert drift <= scale * 0.6, drift
            print('COMPRESSION OK', drift)
            """
        )
        assert "COMPRESSION OK" in out

    def test_compressed_train_step_lowers(self):
        """make_train_step(compress_pods=True) lowers+compiles on a pod mesh
        and the HLO pod-axis payload is int8 (the compression is real)."""
        out = run_with_devices(
            """
            import jax, jax.numpy as jnp
            from jax.sharding import AxisType
            from repro import configs
            from repro.models import lm
            from repro.optim import AdamWConfig, adamw_init
            from repro.training.step import TrainStepConfig, make_train_step

            mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                                 axis_types=(AxisType.Auto,)*3)
            cfg = configs.get_smoke_config('granite3_8b')
            with jax.set_mesh(mesh):
                vals, axes = lm.init_lm_values(jax.random.PRNGKey(0), cfg)
                opt = adamw_init(vals)
                err = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), vals)
                step = make_train_step(cfg, axes, AdamWConfig(),
                                       step_cfg=TrainStepConfig(n_micro=2, compress_pods=True),
                                       mesh=mesh)
                toks = jnp.zeros((8, 16), jnp.int32)
                batch = {'tokens': toks, 'labels': toks}
                lowered = jax.jit(step).lower(vals, opt, batch, err)
                compiled = lowered.compile()
                hlo = compiled.as_text()
                assert 'all-reduce' in hlo
                assert 's8[' in hlo or 's32[' in hlo  # quantised payload present
                # run it for real: loss finite
                v2, o2, m, e2 = jax.jit(step)(vals, opt, batch, err)
                assert bool(jnp.isfinite(m['loss']))
                print('COMPRESSED STEP OK', float(m['loss']))
            """
        )
        assert "COMPRESSED STEP OK" in out


class TestShardedTrainingParity:
    def test_mesh_vs_single_device_loss(self):
        """The same train step on a (2,2) mesh and on 1 device gives the
        same loss (distribution must not change numerics materially)."""
        out = run_with_devices(
            """
            import jax, jax.numpy as jnp
            from jax.sharding import AxisType
            from repro import configs
            from repro.models import lm

            cfg = configs.get_smoke_config('phi35_moe_42b')
            vals, axes = lm.init_lm_values(jax.random.PRNGKey(0), cfg)
            toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
            batch = {'tokens': toks, 'labels': toks}
            l_single, _ = jax.jit(lambda v, b: lm.train_loss(v, cfg, b))(vals, batch)

            mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AxisType.Auto,)*2)
            with jax.set_mesh(mesh):
                l_mesh, _ = jax.jit(lambda v, b: lm.train_loss(v, cfg, b))(vals, batch)
            import numpy as np
            np.testing.assert_allclose(float(l_single), float(l_mesh), rtol=2e-5)
            print('PARITY OK', float(l_single), float(l_mesh))
            """
        )
        assert "PARITY OK" in out

    def test_decode_parity_seq_sharded_cache(self):
        """Decode with a seq-sharded KV cache matches single-device decode."""
        out = run_with_devices(
            """
            import dataclasses
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import AxisType
            from repro import configs
            from repro.models import lm
            from repro.distributed.sharding import rules_for_config, use_rules

            cfg = configs.get_smoke_config('granite_34b')
            cfg = dataclasses.replace(
                cfg, sharding_overrides=(('cache_seq', ('data', 'model')),))
            vals, _ = lm.init_lm_values(jax.random.PRNGKey(0), cfg)
            toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)

            def roll(vals, toks):
                cache = lm.init_cache(cfg, 2, 16)
                logits, cache = lm.prefill(vals, cfg, {'tokens': toks}, cache)
                nxt = jnp.argmax(logits[:, :cfg.vocab_size], -1)[:, None].astype(jnp.int32)
                logits2, cache = lm.decode_step(vals, cfg, nxt, cache)
                return logits, logits2

            l1, l2 = jax.jit(roll)(vals, toks)
            mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AxisType.Auto,)*2)
            with jax.set_mesh(mesh), use_rules(rules_for_config(cfg)):
                m1, m2 = jax.jit(roll)(vals, toks)
            np.testing.assert_allclose(np.asarray(l1), np.asarray(m1), atol=3e-4)
            np.testing.assert_allclose(np.asarray(l2), np.asarray(m2), atol=3e-4)
            print('DECODE PARITY OK')
            """
        )
        assert "DECODE PARITY OK" in out


class TestHLOParser:
    def test_collective_bytes_detects_psum(self):
        out = run_with_devices(
            """
            import jax, jax.numpy as jnp, json
            from jax.sharding import PartitionSpec as P, NamedSharding, AxisType
            from repro.distributed.hlo_analysis import collective_bytes
            mesh = jax.make_mesh((8,), ('x',), axis_types=(AxisType.Auto,))
            def f(a, b):
                return jnp.einsum('ij,jk->ik', a, b)
            with jax.set_mesh(mesh):
                sa = NamedSharding(mesh, P(None, 'x'))
                sb = NamedSharding(mesh, P('x', None))
                low = jax.jit(f, in_shardings=(sa, sb),
                              out_shardings=NamedSharding(mesh, P())).lower(
                    jax.ShapeDtypeStruct((64, 64), jnp.float32),
                    jax.ShapeDtypeStruct((64, 64), jnp.float32))
                hlo = low.compile().as_text()
            stats = collective_bytes(hlo)
            # contracting a sharded axis with replicated output => all-reduce
            # of the (64,64) f32 partials = 16384 bytes
            assert stats.get('all-reduce', 0) >= 16384, stats
            print('PARSER OK', json.dumps(stats))
            """
        )
        assert "PARSER OK" in out
