"""Sharding-rule engine tests (AbstractMesh — no devices needed)."""

import jax
import pytest
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.distributed.sharding import (
    ShardingRules,
    add_zero_axes,
    rules_for_config,
    rules_with_zero,
    spec_for,
)

MESH = AbstractMesh((16, 16), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
MESH3 = AbstractMesh(
    (2, 16, 16), ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3
)


class TestSpecFor:
    def test_batch_over_pod_data(self):
        spec = spec_for(("batch", "seq"), shape=(256, 4096), mesh=MESH3)
        assert spec == P(("pod", "data"))

    def test_divisibility_fallback(self):
        # 25 heads don't divide 16 -> replicated
        spec = spec_for(
            ("embed", "heads", "head_dim"), shape=(1600, 25, 64), mesh=MESH
        )
        assert spec == P()

    def test_divisible_heads_shard(self):
        spec = spec_for(
            ("embed", "heads", "head_dim"), shape=(4096, 32, 128), mesh=MESH
        )
        assert spec == P(None, "model")

    def test_partial_compound_axis(self):
        # batch=1 can't use pod/data; cache_seq override picks up all three
        rules = ShardingRules().replace(cache_seq=("pod", "data", "model"))
        spec = spec_for(
            ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
            rules,
            shape=(32, 1, 524288, 5, 64),
            mesh=MESH3,
        )
        assert spec == P(None, None, ("pod", "data", "model"))

    def test_used_axis_skipped_not_dropped(self):
        # batch claims pod+data; cache_seq still gets model
        rules = ShardingRules().replace(cache_seq=("pod", "data", "model"))
        spec = spec_for(
            ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
            rules,
            shape=(88, 128, 32768, 1, 128),
            mesh=MESH3,
        )
        assert spec == P(None, ("pod", "data"), "model")

    def test_no_mesh_returns_none(self):
        assert spec_for(("batch",), shape=(8,), mesh=None) is None

    def test_vocab_sharding(self):
        spec = spec_for(("embed", "vocab"), shape=(4096, 49408), mesh=MESH)
        assert spec == P(None, "model")

    def test_odd_vocab_padded_divisible(self):
        # 49155 -> padded 49408 = 256*193; raw odd vocab would replicate
        raw = spec_for(("vocab",), shape=(49155,), mesh=MESH)
        padded = spec_for(("vocab",), shape=(49408,), mesh=MESH)
        assert raw == P()
        assert padded == P("model")


class TestZeroAxes:
    def test_zero_extends_replicated_dim(self):
        axes = add_zero_axes(
            ("embed", "heads", "head_dim"), (4096, 32, 128), mesh=MESH
        )
        assert axes == ("_zero", "heads", "head_dim")
        spec = spec_for(axes, rules_with_zero(), shape=(4096, 32, 128), mesh=MESH)
        assert spec == P(("data",), "model") or spec == P("data", "model")

    def test_zero_skips_indivisible(self):
        axes = add_zero_axes(("heads",), (25,), mesh=MESH)
        assert axes == ("heads",)

    def test_zero_on_3d_mesh(self):
        axes = add_zero_axes(("embed", "ffn"), (4096, 12800), mesh=MESH3)
        assert axes == ("_zero", "ffn")
        spec = spec_for(axes, rules_with_zero(), shape=(4096, 12800), mesh=MESH3)
        assert spec == P(("pod", "data"), "model")


class TestConfigOverrides:
    def test_rules_for_config(self):
        from repro import configs

        cfg = configs.get_config("granite_34b")
        rules = rules_for_config(cfg)
        assert rules.as_dict()["cache_seq"] == ("pod", "data", "model")

    def test_default_rules_unpolluted(self):
        assert ShardingRules().as_dict()["cache_seq"] is None
