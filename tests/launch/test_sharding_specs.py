"""Sharding policy unit tests: divisibility-aware spec rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import AbstractMesh

from repro.configs import get_config
from repro.sharding import specs as SH


@pytest.fixture(scope="module")
def mesh():
    # spec rules read mesh sizes: a device-free mesh of the real shape
    return AbstractMesh((16, 16), ("data", "model"))


class TestParamSpecRules:
    def test_embedding_shards_vocab(self, mesh):
        cfg = get_config("gemma2-9b")
        spec = SH.param_spec(cfg, "embed", (256000, 3584), mesh)
        assert spec[0] == "model" and spec[1] is None

    def test_gqa_divisible_heads(self, mesh):
        cfg = get_config("yi-9b")                 # 32H, kv=4
        wq = SH.param_spec(cfg, "unit/0/attn/wq", (48, 4096, 32, 128),
                           mesh)
        assert wq[2] == "model"                   # heads sharded
        wk = SH.param_spec(cfg, "unit/0/attn/wk", (48, 4096, 4, 128),
                           mesh)
        assert all(s is None for s in wk)         # kv<tp: replicated

    def test_context_parallel_replicates_attention(self, mesh):
        cfg = get_config("phi3-medium-14b")       # 40H: seq-parallel
        assert cfg.attn_sequence_parallel
        wq = SH.param_spec(cfg, "unit/0/attn/wq", (40, 5120, 40, 128),
                           mesh)
        assert all(s is None for s in wq)

    def test_experts_shard_on_model(self, mesh):
        cfg = get_config("qwen3-moe-30b-a3b")
        w = SH.param_spec(cfg, "unit/0/moe/w_up", (48, 128, 2048, 768),
                          mesh)
        assert w[1] == "model"

    def test_mlp_column_row(self, mesh):
        cfg = get_config("yi-9b")
        up = SH.param_spec(cfg, "unit/0/mlp/up", (48, 4096, 11008), mesh)
        down = SH.param_spec(cfg, "unit/0/mlp/down", (48, 11008, 4096),
                             mesh)
        assert up[2] == "model" and down[1] == "model"

    def test_norms_replicated(self, mesh):
        cfg = get_config("yi-9b")
        ln = SH.param_spec(cfg, "unit/0/ln1", (48, 4096), mesh)
        assert all(s is None for s in ln)


class TestZero1:
    def test_adds_data_axis_on_free_dim(self, mesh):
        from jax.sharding import PartitionSpec as P
        spec = SH.zero1_spec(P(None, "model"), (4096, 11008), mesh)
        assert spec[0] == "data"                  # 4096 % 16 == 0

    def test_skips_when_nothing_divides(self, mesh):
        from jax.sharding import PartitionSpec as P
        spec = SH.zero1_spec(P(), (7,), mesh)
        assert all(s is None for s in spec)


class TestBatchSpec:
    def test_composes_pod_and_data(self):
        m = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
        spec = SH.batch_spec(m, 256)
        assert spec[0] == ("pod", "data")

    def test_batch_one_unsharded(self):
        m = AbstractMesh((16, 16), ("data", "model"))
        spec = SH.batch_spec(m, 1)
        assert spec[0] is None
