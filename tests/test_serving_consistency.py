"""Decode/prefill consistency + shard_map MoE path equivalence.

These pin the §Perf optimizations to the reference semantics:
  * grouped-GQA decode (no repeat) must agree with prefill logits;
  * the ep_sharded shard_map dispatch must match the default GSPMD path
    (run in an 8-fake-device subprocess).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import decode_step, init_caches, init_params, prefill_step


@pytest.mark.parametrize("arch", ["qwen3-8b", "gemma2-2b", "musicgen-large",
                                  "phi3.5-moe-42b-a6.6b"])
def test_decode_matches_prefill(arch):
    """prefill(n) + decode(tok n+1) == prefill(n+1) last-position logits."""
    import dataclasses
    cfg = smoke_config(arch)
    if cfg.moe is not None:
        # capacity drops differ between a 9-token prefill and a 1-token
        # decode (expected capacity-MoE semantics); crank capacity so the
        # comparison isolates numerics from drop policy
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab)
    c1 = init_caches(cfg, 2, 16)
    _, c1 = prefill_step(params, cfg, {"tokens": toks[:, :8]}, c1,
                         use_kernel=False)
    ld, _ = decode_step(params, cfg, {"tokens": toks[:, 8:9]}, c1,
                        use_kernel=False)
    c2 = init_caches(cfg, 2, 16)
    lp, _ = prefill_step(params, cfg, {"tokens": toks}, c2,
                         use_kernel=False)
    # bf16 KV cache tolerance
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lp),
                               atol=2e-2, rtol=2e-2)


SHARD_MAP_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.compat import make_mesh
    from repro.configs import smoke_config
    from repro.models.moe import moe_apply, moe_init
    from repro.sharding import ShardingRules, use_rules

    cfg = smoke_config("phi3.5-moe-42b-a6.6b")   # 8 experts, top-2
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

    y_ref, aux_ref, m_ref = moe_apply(params, cfg, x, use_kernel=False)

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = ShardingRules.for_mesh(mesh, profile="ep_sharded")
    with mesh, use_rules(rules):
        y_sm, aux_sm, m_sm = jax.jit(
            lambda p, x: moe_apply(p, cfg, x, use_kernel=False))(params, x)

    # routing is token-local and identical; capacity differs (local vs
    # global buckets) so only compare where neither path dropped tokens
    assert int(m_ref["moe/dropped"]) == 0, m_ref
    assert int(m_sm["moe/dropped"]) == 0, m_sm
    err = float(jnp.abs(y_sm - y_ref).max())
    assert err < 2e-3, err
    print("SHARDMAP-OK", err)
""")


def test_shard_map_moe_matches_default():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SHARD_MAP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDMAP-OK" in out.stdout


def test_partition_to_permutation_empty_parts():
    from repro.core import partition_to_permutation
    parts = np.array([0, 0, 2, 2, 0])          # part 1 and 3 empty
    perm, splits = partition_to_permutation(parts, 4)
    assert len(splits) == 5
    assert splits[-1] == 5
    assert splits[1] == splits[2]               # empty part 1
