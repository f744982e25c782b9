"""Sharding rules + roofline HLO parsing (no multi-device needed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.configs import SHAPES, get_config
from repro.launch.roofline import (bytes_model, collective_bytes_from_hlo,
                                   model_flops)
from repro.sharding import ShardingRules, param_pspecs, shard, use_rules


def test_param_pspecs_name_rules():
    params = {
        "embed": jnp.zeros((100, 16)),
        "period": {"pos0": {
            "attn": {"wq": jnp.zeros((4, 16, 32)),
                     "wo": jnp.zeros((4, 32, 16))},
            "mlp": {"w_up": jnp.zeros((4, 16, 64)),
                    "w_down": jnp.zeros((4, 64, 16))},
            "moe": {"router": jnp.zeros((4, 16, 8)),
                    "experts_up": jnp.zeros((4, 8, 16, 32))},
            "norm_mix": {"scale": jnp.zeros((4, 16))},
        }},
    }
    rules = ShardingRules(batch=("data",), fsdp="data", tp="model",
                          tp_size=4, batch_size=4)
    specs = param_pspecs(params, rules)
    assert specs["embed"] == P("model", "data")
    pos = specs["period"]["pos0"]
    assert pos["attn"]["wq"] == P(None, "data", "model")
    assert pos["attn"]["wo"] == P(None, "model", "data")
    assert pos["mlp"]["w_down"] == P(None, "model", "data")
    assert pos["moe"]["experts_up"] == P(None, "model", None, None)
    assert pos["norm_mix"]["scale"] == P(None, None)


def test_shard_noop_without_rules():
    x = jnp.zeros((4, 4))
    assert shard(x, "batch", None) is x


def test_shard_divisibility_guard():
    """Indivisible dims must not be constrained (gemma2 8 heads / tp16)."""
    mesh = make_mesh((1,), ("data",))
    rules = ShardingRules(batch=("data",), fsdp="data", tp=None, sp=None,
                          tp_size=16, batch_size=1)
    with mesh, use_rules(rules):
        x = jnp.zeros((8, 4))
        y = shard(x, "tp", None)     # 8 % 16 != 0 -> unconstrained
        assert y.shape == x.shape
        z = shard(jnp.zeros((3, 4)), "batch", None)  # 3 % 1 == 0 -> ok
        assert z.shape == (3, 4)


SAMPLE_HLO = """
HloModule test
ENTRY main {
  %p0 = bf16[16,512]{1,0} parameter(0)
  %ag = bf16[16,8192]{1,0} all-gather(%p0), dimensions={1}
  %ar = f32[128,256]{1,0} all-reduce(%x), to_apply=%add
  %rs = f32[8,256]{1,0} reduce-scatter(%y), dimensions={0}
  %cp = u32[4]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %a2a = (f32[2,4]{1,0}, f32[2,4]{1,0}) all-to-all(%w, %v), dimensions={0}
  %ags = bf16[32,32]{1,0} all-gather-start(%q), dimensions={0}
  %agd = bf16[32,32]{1,0} all-gather-done(%ags)
}
"""


def test_collective_parser():
    out = collective_bytes_from_hlo(SAMPLE_HLO)
    assert out["all-gather"] == 16 * 8192 * 2 + 32 * 32 * 2  # ag + ag-start
    assert out["all-reduce"] == 128 * 256 * 4
    assert out["reduce-scatter"] == 8 * 256 * 4
    assert out["collective-permute"] == 4 * 4
    assert out["all-to-all"] == 2 * (2 * 4 * 4)
    assert out["count"] == 6  # -done not counted


def test_model_flops_kinds():
    cfg = get_config("qwen3-8b")
    f_train = model_flops(cfg, SHAPES["train_4k"])
    f_prefill = model_flops(cfg, SHAPES["prefill_32k"])
    f_decode = model_flops(cfg, SHAPES["decode_32k"])
    n = cfg.active_param_count()
    assert f_train == pytest.approx(6 * n * 256 * 4096)
    assert f_prefill == pytest.approx(2 * n * 32 * 32768)
    assert f_decode == pytest.approx(2 * n * 128)


def test_bytes_model_sane():
    cfg = get_config("qwen3-8b")
    b_train = bytes_model(cfg, SHAPES["train_4k"])
    b_decode = bytes_model(cfg, SHAPES["decode_32k"])
    # training moves far more bytes than one decode step
    assert b_train > 10 * b_decode
    # decode is at least one pass over the TP weight shard
    assert b_decode > 2.0 * cfg.param_count() / 16 * 0.5
