"""MoE layer — the paper's 1D SpGEMM transplanted to expert parallelism.

The router's token→expert assignment is a sparse boolean matrix R
(tokens × experts, top-k nonzeros per row). Dispatch computes Xᵉ = RᵀX and
combine Y = R·(gates ⊙ FFNᵉ(Xᵉ)) — sparse-sparse products in the paper's
1D layout: expert weights are the stationary B (sharded over 'model' = the
1D process grid), tokens are the fetched A.

Algorithm-1/2 mapping (DESIGN.md §3):

  * symbolic phase   = router top-k + capacity bucketing (on device but
    *static-shaped*: capacity C is the plan)
  * block fetch      = whole (expert, capacity) buckets move — bounded
    over-fetch (padding slots) for a fixed fragment count, exactly the
    paper's ≤K RDMA messages per peer
  * RDMA fetch       = the all-to-all that moves buckets to expert owners
  * local SpGEMM     = the grouped expert GEMM Pallas kernel

Two execution paths share ``_route_and_combine``:

  * default — single jit program; the (E, C, d) buckets carry a sharding
    constraint and GSPMD infers the all-to-all. Simple, but GSPMD cannot
    shard the dispatch *scatter* and replicates it (measured ~30× extra
    collective bytes at train_4k scale — EXPERIMENTS.md §Perf).
  * ep_sharded (shard_map) — tokens arrive (batch × seq)-sharded, each
    device routes and buckets its local slab, and ONE tiled all-to-all
    over 'model' delivers expert buckets to their owners (the MPI_Get of
    the original, with bucket = block). Enabled by the ``ep_sharded``
    sharding profile.

Load metrics mirror the paper's accounting: exact routed tokens (required
bytes) vs capacity slots (fetched bytes).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..compat import shard_map
from ..configs.base import ModelConfig, MoEConfig
from ..kernels.moe_gemm import grouped_gemm
from ..sharding import current_rules, shard
from .layers import dense_init, mlp_apply, mlp_init

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32):
    moe = cfg.moe
    d = cfg.d_model
    e = moe.n_experts_padded
    ks = jax.random.split(key, 5)
    scale = d ** -0.5
    p = {
        "router": dense_init(ks[0], d, e, dtype),
        "experts_up": (jax.random.truncated_normal(
            ks[1], -2, 2, (e, d, moe.d_ff_expert)) * scale).astype(dtype),
        "experts_down": (jax.random.truncated_normal(
            ks[2], -2, 2, (e, moe.d_ff_expert, d))
            * moe.d_ff_expert ** -0.5).astype(dtype),
    }
    if cfg.mlp in ("swiglu", "geglu"):
        p["experts_gate"] = (jax.random.truncated_normal(
            ks[3], -2, 2, (e, d, moe.d_ff_expert)) * scale).astype(dtype)
    if moe.n_shared:
        p["shared"] = mlp_init(ks[4], d, moe.n_shared * moe.d_ff_shared,
                               cfg.mlp, dtype)
    return p


def _capacity(moe: MoEConfig, n_tokens: int) -> int:
    c = int(n_tokens * moe.top_k / moe.n_experts * moe.capacity_factor)
    return max(8, -(-c // 8) * 8)  # multiple of 8 lanes


def _expert_ffn(cfg: ModelConfig, bkts, eg, eu, ed,
                use_kernel: bool, interpret: bool):
    up = grouped_gemm(bkts, eu, use_kernel=use_kernel, interpret=interpret)
    if eg is not None:
        g = grouped_gemm(bkts, eg, use_kernel=use_kernel,
                         interpret=interpret)
        h = (jax.nn.silu(g) if cfg.mlp == "swiglu"
             else jax.nn.gelu(g, approximate=True)) * up
    else:
        r = jax.nn.relu(up)
        h = r * r
    return grouped_gemm(h, ed, use_kernel=use_kernel, interpret=interpret)


def _route_and_combine(cfg: ModelConfig, router, shared, xf,
                       run_experts: Callable):
    """Routing + capacity bucketing + combine on a flat (T, d) slab.

    ``run_experts``: (E, C, d) buckets -> (E, C, d) outputs; the two
    execution paths differ only in how this function moves the buckets.
    """
    moe = cfg.moe
    t, d = xf.shape
    e = moe.n_experts_padded
    k = moe.top_k
    cap = _capacity(moe, t)

    logits = (xf @ router).astype(jnp.float32)               # (T, E)
    if e > moe.n_experts:
        logits = jnp.where(jnp.arange(e)[None, :] >= moe.n_experts,
                           -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)                     # (T, k)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)

    # ---- symbolic phase: capacity-bucketed dispatch plan -------------------
    flat_e = ids.reshape(-1)                                 # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e)                              # stable
    se, st_, sg = flat_e[order], flat_t[order], flat_g[order]
    run_start = jnp.searchsorted(se, jnp.arange(e))          # (E,)
    rank = jnp.arange(t * k) - run_start[se]
    keep = rank < cap                                        # capacity drop
    slot = se * cap + jnp.clip(rank, 0, cap - 1)             # (T*k,)

    buckets = jnp.zeros((e * cap, d), xf.dtype)
    buckets = buckets.at[slot].add(jnp.where(keep[:, None], xf[st_], 0.0))
    out = run_experts(buckets.reshape(e, cap, d)).reshape(e * cap, d)

    # ---- combine: Y = R (gates ⊙ expert outputs) ---------------------------
    y = jnp.zeros((t, d), xf.dtype)
    y = y.at[st_].add(out[slot] * (sg * keep)[:, None].astype(xf.dtype))
    if shared is not None:
        y = y + mlp_apply(shared, xf, cfg.mlp)

    # ---- aux: load balancing + paper-style traffic accounting --------------
    frac_tokens = jnp.zeros(e, jnp.float32).at[flat_e].add(1.0) / (t * k)
    aux = moe.n_experts * jnp.sum(frac_tokens * probs.mean(0)) \
        * moe.router_aux_weight
    metrics = {
        "moe/routed_tokens": keep.sum(),             # exact (required)
        "moe/capacity_slots": jnp.asarray(e * cap),  # fetched (padded)
        "moe/dropped": (~keep).sum(),
    }
    return y, aux, metrics


def _moe_shard_map(params, cfg: ModelConfig, x, rules,
                   use_kernel: bool, interpret: bool):
    """Explicit EP: local routing + tiled all-to-all bucket exchange.

    Two token layouts, set by the sharding profile:
      * ep_sharded (TP active): tokens arrive batch×seq-sharded — seq over
        the expert axis, so every device owns a distinct slab.
      * ep_dp (no TP): the expert axis is part of data parallelism; tokens
        are already fully batch-sharded and the seq dim stays whole.
    """
    from jax.sharding import PartitionSpec as P

    moe = cfg.moe
    b, s, d = x.shape
    model = rules.expert_axis
    batch_axes = rules.batch
    seq_split = rules.tp is not None  # ep_sharded: seq over the ep axis

    x = shard(x, "batch", "seq_sp" if seq_split else None, None)

    def local(x_loc, router, eg, eu, ed, shared):
        bl, sl, _ = x_loc.shape
        xf = x_loc.reshape(bl * sl, d)

        def run(bkts):
            from jax.ad_checkpoint import checkpoint_name
            bkts = jax.lax.all_to_all(bkts, model, split_axis=0,
                                      concat_axis=1, tiled=True)
            # names let the remat policy keep a2a results across the
            # checkpoint boundary — the backward re-uses them instead of
            # re-dispatching (§Perf qwen2-moe iteration 5)
            bkts = checkpoint_name(bkts, "moe_a2a_fwd")
            out = _expert_ffn(cfg, bkts, eg, eu, ed, use_kernel, interpret)
            out = jax.lax.all_to_all(out, model, split_axis=1,
                                     concat_axis=0, tiled=True)
            return checkpoint_name(out, "moe_a2a_ret")

        y, aux, metrics = _route_and_combine(cfg, router, shared, xf, run)
        all_axes = tuple(dict.fromkeys(
            tuple(batch_axes or ()) + (model,)))
        aux = jax.lax.pmean(aux, all_axes)
        metrics = {k2: jax.lax.psum(v, all_axes)
                   for k2, v in metrics.items()}
        return y.reshape(bl, sl, d), aux, metrics

    x_spec = P(batch_axes, model, None) if seq_split \
        else P(batch_axes, None, None)
    in_specs = (
        x_spec,
        P(None, None),                               # router replicated
        P(model, None, None) if "experts_gate" in params else None,
        P(model, None, None),                        # experts_up
        P(model, None, None),                        # experts_down
        jax.tree.map(lambda _: P(None, None), params["shared"])
        if moe.n_shared else None,
    )
    out_specs = (x_spec, P(),
                 {"moe/routed_tokens": P(), "moe/capacity_slots": P(),
                  "moe/dropped": P()})

    # check_rep off: with use_kernel the body holds the grouped-GEMM
    # pallas_call, whose out_shape carries no vma (see repro.compat.shard_map)
    fn = shard_map(local, mesh=rules.mesh,
                   in_specs=in_specs, out_specs=out_specs,
                   check_rep=False)
    y, aux, metrics = fn(
        x, params["router"], params.get("experts_gate"),
        params["experts_up"], params["experts_down"],
        params.get("shared") if moe.n_shared else None)
    return shard(y, "batch", None, None), aux, metrics


def moe_apply(params, cfg: ModelConfig, x,
              *, use_kernel: bool = True,
              interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array, dict]:
    """x: (B, S, d) -> (y, aux_loss, metrics)."""
    rules = current_rules()
    moe = cfg.moe
    b, s, d = x.shape

    if (rules is not None and rules.ep_shard_map
            and rules.expert_axis is not None
            and rules.mesh is not None
            and (rules.tp is None or s % rules.tp_size == 0)
            and b % max(rules.batch_size, 1) == 0
            and moe.n_experts_padded
            % rules.mesh.shape[rules.expert_axis] == 0):
        return _moe_shard_map(params, cfg, x, rules, use_kernel, interpret)

    eg = params.get("experts_gate")
    shared = params.get("shared") if moe.n_shared else None

    def run(bkts):
        bkts = shard(bkts, "tp", None, None)         # EP reshard (GSPMD a2a)
        out = _expert_ffn(cfg, bkts, eg, params["experts_up"],
                          params["experts_down"], use_kernel, interpret)
        return shard(out, "tp", None, None)

    y, aux, metrics = _route_and_combine(
        cfg, params["router"], shared, x.reshape(b * s, d), run)
    return y.reshape(b, s, d), aux, metrics
