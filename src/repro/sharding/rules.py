"""Logical-axis sharding rules: name-based PartitionSpecs (MaxText-style).

Parallelism scheme over the production meshes
``(data=16, model=16)`` / ``(pod=2, data=16, model=16)``:

  * DP/FSDP — batch over ``(pod, data)``; parameters ZeRO-sharded over
    ``data`` on their largest non-TP dimension (all-gathered per scan step).
  * TP — Megatron pairs: Q/K/V & up-projections column-sharded over
    ``model``, output & down-projections row-sharded, so each block incurs
    one reduce(-scatter) on the residual, not four.
  * EP — MoE expert dim over ``model`` (experts padded to a multiple).
  * SP — long-context KV caches sequence-sharded over ``model``; GSPMD
    inserts the partial-softmax (flash-decoding-style LSE) reductions.

Two entry points:

  * :func:`param_pspecs` — maps a params pytree to PartitionSpecs by leaf
    *path name* (the rules table below).
  * :func:`shard` — activation constraint helper usable inside model code;
    a no-op unless a :class:`ShardingRules` context is active, so smoke
    tests on one CPU device run the same code path unconstrained.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

__all__ = [
    "AXIS_POD", "AXIS_DATA", "AXIS_MODEL",
    "ShardingRules", "use_rules", "current_rules", "shard", "param_pspecs",
]

AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_MODEL = "model"


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Resolved logical axes for one mesh."""

    batch: Tuple[str, ...]           # ('pod', 'data') or ('data',)
    fsdp: Optional[str] = AXIS_DATA  # ZeRO shard axis for params
    tp: Optional[str] = AXIS_MODEL   # tensor-parallel axis
    sp: Optional[str] = AXIS_MODEL   # sequence-parallel axis (KV caches)
    # divisibility context for conditional activation shardings
    tp_size: int = 1
    fsdp_size: int = 1
    batch_size: int = 1              # product of batch mesh axes
    # explicit shard_map expert-parallel dispatch (hillclimb: the paper's
    # Algorithm 1 done with hand-placed a2a instead of GSPMD inference)
    ep_shard_map: bool = False
    ep_axis: Optional[str] = None    # expert-shard axis (defaults to tp)
    mesh: Optional[object] = dataclasses.field(
        default=None, compare=False, hash=False)

    @property
    def expert_axis(self) -> Optional[str]:
        return self.ep_axis or self.tp

    @staticmethod
    def for_mesh(mesh, profile: str = "default") -> "ShardingRules":
        """Resolve a parallelism *profile* onto a mesh.

        default   : DP over (pod, data) + FSDP over data + TP/EP/SP over
                    model — the safe starting point for every cell.
        dp_only   : no tensor parallelism; the model axis joins data
                    parallelism (batch over pod×data×model, params FSDP
                    over data). Right for small-d models whose TP
                    all-reduces dwarf their matmuls (musicgen d=2048).
        serve_tp  : inference profile — no FSDP (no per-step param
                    all-gathers; params live sharded over model only),
                    batch over (pod, data), KV caches sequence-sharded.
        ep_sharded: like default, but MoE dispatch/combine runs as an
                    explicit shard_map all-to-all (the paper's Algorithm 1
                    with hand-placed communication) instead of relying on
                    GSPMD to infer a scatter sharding.
        ep_dp     : expert parallelism WITHOUT tensor parallelism — batch
                    over pod×data×model (attention/MLP pure DP, no
                    per-layer activation all-reduces), experts sharded
                    over 'model' with the shard_map a2a. The right shape
                    for small-d MoEs (qwen2-moe d=2048).
        """
        names = mesh.axis_names
        has_model = AXIS_MODEL in names
        ep = False
        ep_axis = None
        if profile in ("default", "ep_sharded"):
            ep = profile == "ep_sharded"
            batch = tuple(n for n in (AXIS_POD, AXIS_DATA) if n in names)
            fsdp = AXIS_DATA if AXIS_DATA in names else None
            tp = AXIS_MODEL if has_model else None
        elif profile == "ep_dp":
            ep = True
            ep_axis = AXIS_MODEL if has_model else None
            batch = tuple(n for n in (AXIS_POD, AXIS_DATA, AXIS_MODEL)
                          if n in names)
            fsdp = AXIS_DATA if AXIS_DATA in names else None
            tp = None
        elif profile == "dp_only":
            batch = tuple(n for n in (AXIS_POD, AXIS_DATA, AXIS_MODEL)
                          if n in names)
            fsdp = AXIS_DATA if AXIS_DATA in names else None
            tp = None
        elif profile == "serve_tp":
            batch = tuple(n for n in (AXIS_POD, AXIS_DATA) if n in names)
            fsdp = None
            tp = AXIS_MODEL if has_model else None
        else:  # pragma: no cover
            raise ValueError(f"unknown profile {profile!r}")
        bsz = 1
        for n in batch:
            bsz *= mesh.shape[n]
        return ShardingRules(
            batch=batch, fsdp=fsdp, tp=tp, sp=tp,
            tp_size=mesh.shape[AXIS_MODEL] if tp else 1,
            fsdp_size=mesh.shape[AXIS_DATA] if fsdp else 1,
            batch_size=bsz,
            ep_shard_map=ep, ep_axis=ep_axis, mesh=mesh,
        )


_ctx = threading.local()


def current_rules() -> Optional[ShardingRules]:
    return getattr(_ctx, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[ShardingRules]):
    prev = current_rules()
    _ctx.rules = rules
    try:
        yield rules
    finally:
        _ctx.rules = prev


def shard(x, *logical: Optional[str]):
    """Constrain activation ``x`` to logical axes; no-op without rules.

    Logical names: 'batch', 'seq_sp', 'tp', 'fsdp', None (replicated).
    A dim whose concrete size does not divide the mesh-axis size is left
    unconstrained (e.g. gemma2's 8 heads on a 16-way model axis).
    """
    rules = current_rules()
    if rules is None:
        return x

    spec = []
    for i, name in enumerate(logical):
        dim = x.shape[i]
        if name is None:
            spec.append(None)
        elif name == "batch":
            ok = rules.batch and dim % max(rules.batch_size, 1) == 0
            spec.append(rules.batch if ok else None)
        elif name == "batch_nm":
            # batch axes excluding the model/expert axis — used where a
            # later dim must shard over 'model' (e.g. vocab-sharded CE)
            axes = tuple(a for a in (rules.batch or ())
                         if a != AXIS_MODEL)
            sz = 1
            if rules.mesh is not None:
                for a in axes:
                    sz *= rules.mesh.shape[a]
            ok = axes and dim % max(sz, 1) == 0
            spec.append(axes if ok else None)
        elif name == "vocab":
            ax = rules.tp or rules.expert_axis
            sz = (rules.mesh.shape[ax]
                  if (ax and rules.mesh is not None) else rules.tp_size)
            ok = ax is not None and dim % max(sz, 1) == 0
            spec.append(ax if ok else None)
        elif name in ("tp", "seq_sp"):
            ax = rules.tp if name == "tp" else rules.sp
            ok = ax is not None and dim % max(rules.tp_size, 1) == 0
            spec.append(ax if ok else None)
        elif name == "fsdp":
            spec.append(rules.fsdp)
        else:  # pragma: no cover
            raise ValueError(f"unknown logical axis {name!r}")
    return jax.lax.with_sharding_constraint(x, P(*_dedupe_axes(spec)))


def _dedupe_axes(spec):
    """A mesh axis may shard at most one dim: a later dim drops any axis an
    earlier dim already uses (batch and fsdp can both map to 'data')."""
    used = set()
    out = []
    for entry in spec:
        axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
        keep = tuple(a for a in axes if a not in used)
        used.update(keep)
        if not keep:
            out.append(None)
        else:
            out.append(keep[0] if isinstance(entry, str) else keep)
    return out


# ---------------------------------------------------------------------------
# parameter rules — matched against the pytree path (joined with '/')
# ---------------------------------------------------------------------------
# Conventions (see models/): projections stored flat —
#   wq/wk/wv : (d_model, H*hd)      col-sharded (fsdp, tp)
#   wo       : (H*hd, d_model)      row-sharded (tp, fsdp)
#   w_up/w_gate : (d_model, d_ff)   col-sharded (fsdp, tp)
#   w_down   : (d_ff, d_model)      row-sharded (tp, fsdp)
#   embed    : (vocab, d_model)     vocab over tp (sharded logits/softmax)
#   experts_*: (E, ...)             expert dim over tp (EP)
#   mamba in/out projections        like mlp
# Leading layer-stack dims (from scan-over-layers) get None prepended.

_RULES = [
    (r"embed$",                     ("vocab", "fsdp")),
    (r"(wq|wk|wv|wqkv)$",           ("fsdp", "tp")),
    (r"wo$",                        ("tp", "fsdp")),
    (r"(w_up|w_gate|w_in)$",        ("fsdp", "tp")),
    (r"w_down|w_out$",              ("tp", "fsdp")),
    (r"experts_up$",                ("ep", None, None)),
    (r"experts_gate$",              ("ep", None, None)),
    (r"experts_down$",              ("ep", None, None)),
    (r"router$",                    ("fsdp", None)),
    (r"(a_log|dt_bias|d_skip)$",    (None,)),
    (r"conv_w$",                    (None, "tp")),
    (r"(norm|scale|bias|qnorm|knorm)", (None,)),
]


def _spec_for(path: str, shape, rules: ShardingRules) -> P:
    ndim = len(shape)
    for pat, logical in _RULES:
        if re.search(pat, path):
            resolved = []
            for name in logical:
                if name == "tp":
                    resolved.append((rules.tp, rules.tp_size))
                elif name == "vocab":
                    # vocab shards over tp when active, else the expert/
                    # model axis (keeps the big embedding + CE sharded
                    # under ep_dp / dp_only too)
                    ax = rules.tp or rules.expert_axis
                    sz = (rules.mesh.shape[ax]
                          if (ax and rules.mesh) else rules.tp_size)
                    resolved.append((ax, sz))
                elif name == "ep":
                    ax = rules.expert_axis
                    sz = rules.mesh.shape[ax] if (ax and rules.mesh) \
                        else rules.tp_size
                    resolved.append((ax, sz))
                elif name == "fsdp":
                    resolved.append((rules.fsdp, rules.fsdp_size))
                else:
                    resolved.append((None, 1))
            # prepend None for stacked leading dims (scan-over-layers)
            while len(resolved) < ndim:
                resolved.insert(0, (None, 1))
            resolved = resolved[-ndim:] if ndim else []
            # drop axes whose dim is not divisible by the axis size
            # (e.g. mamba2's 50280-row vocab on a 16-way model axis)
            final = [ax if ax and d % max(sz, 1) == 0 else None
                     for (ax, sz), d in zip(resolved, shape)]
            return P(*final)
    return P(*([None] * ndim))


def param_pspecs(params, rules: ShardingRules):
    """PartitionSpec pytree mirroring ``params`` via the name rules."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        name = "/".join(
            getattr(k, "key", getattr(k, "name", str(k))) for k in path)
        specs.append(_spec_for(name, leaf.shape, rules))
    return jax.tree_util.tree_unflatten(treedef, specs)
