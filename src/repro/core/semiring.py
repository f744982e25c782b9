"""Semirings for SpGEMM — the host *and* device contract.

The betweenness-centrality application multiplies over non-arithmetic
semirings (boolean or-and for BFS frontier expansion; plus-times for path
counting and the backward sweep). The local SpGEMM in ``local_spgemm.py`` and
the distributed algorithms are all parameterized over a :class:`Semiring`.

Each semiring supplies two layers of the same algebra:

  * **host (numpy)**: the scalar multiply, a segment-reduce for the additive
    monoid, and the additive identity used to prune explicit zeros;
  * **device (jnp / Pallas)**: the dense-tile contract the block-sparse
    engines consume — a batched tile "matmul" (``jnp_matmul``), the additive
    combine (``jnp_add``), a kernel-side fused combine for one ``(bs, bs)``
    accumulator step (``jnp_tile_combine``), and a segment-reduce over the
    additive monoid (``jnp_segment_reduce``).

The device engines must **never** spell a literal ``0.0``: every payload pad,
accumulator reset, empty-schedule output and decode prune goes through
``Semiring.zero`` / ``prune_mask`` (ROADMAP "semiring contract" policy).
This works because in all registered semirings the additive identity is also
the multiplicative annihilator (0 for +·, 0 for ∨∧, +inf for min-plus), so
identity-padded dense tiles multiply to identity contributions at absent
positions.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

__all__ = ["Semiring", "PLUS_TIMES", "BOOL_OR_AND", "MIN_PLUS", "by_name"]

_MXU_PASSES = {"DEFAULT": 1, "HIGHEST": 6}


@dataclasses.dataclass(frozen=True)
class Semiring:
    name: str
    # scalar/vector multiply on numpy arrays
    mul: Callable[[np.ndarray, np.ndarray], np.ndarray]
    # segment-reduce of the additive monoid: (vals, segment_starts) -> reduced
    add_reduceat: Callable[[np.ndarray, np.ndarray], np.ndarray]
    # additive identity (entries equal to this are pruned from results);
    # doubles as the multiplicative annihilator in all registered semirings,
    # so it is the correct fill for absent positions of dense tiles
    zero: float
    # jnp-side ops for dense-tile execution (a/b: [..., bs, bs] tile stacks)
    jnp_matmul: Callable  # (a_tiles, b_tiles) -> c_tiles contribution
    jnp_add: Callable     # (acc, contribution) -> acc
    # kernel-side fused step on one (bs, bs) accumulator:
    #   acc <- acc (+) a ⊗ b    — plus-times keeps the MXU jnp.dot path
    jnp_tile_combine: Callable = None
    # segment-reduce of the additive monoid on device:
    #   (vals [nprod, ...], segment_ids, num_segments) -> [num_segments, ...]
    # empty segments come back as the reduce identity of the underlying op
    jnp_segment_reduce: Callable = None
    # collective reduce of the additive monoid over a named mesh axis:
    #   (vals, axis_name) -> vals  — the Split-3D cross-layer merge
    # (psum / pmax / pmin: every registered monoid has a native collective)
    jnp_axis_reduce: Callable = None
    # the jax.lax.Precision of the MXU dot in jnp_matmul and
    # jnp_tile_combine (the factories pass this same value to the dot);
    # None for a combine with no dot
    dot_precision: object = None

    @property
    def mxu_passes(self) -> int:
        """bfloat16 MXU passes per float32 tile product, from
        ``dot_precision``: 0 without a dot, 1 at DEFAULT, 6 at HIGHEST.
        The 6 is assumed, XLA's count for a float32 dot at HIGHEST; how
        many passes Mosaic's ``contract_precision<fp32>`` makes was not
        measured. Recorded on the dispatch span (``mxu_passes``)."""
        if self.dot_precision is None:
            return 0
        return _MXU_PASSES[self.dot_precision.name]

    def prune_mask(self, vals: np.ndarray, tol: float = 0.0) -> np.ndarray:
        """Entries considered nonzero by this semiring: |v - 0̄| > tol for
        a finite identity. For an infinite identity (min-plus) the mask is
        exactly the finite entries and ``tol`` has no effect — every
        finite value is infinitely far from the identity, so there is no
        meaningful near-identity band to drop."""
        if np.isinf(self.zero):
            return np.isfinite(vals)
        return np.abs(vals - self.zero) > tol

    def fill(self, shape, dtype=np.float32) -> np.ndarray:
        """Host-side array of additive identities (payload-pad fill)."""
        return np.full(shape, self.zero, dtype=dtype)


def _make_plus_times() -> Semiring:
    import jax
    import jax.numpy as jnp

    # float32 to float32's rounding: a dot with no precision is one
    # bfloat16 pass on the TPU (Pallas gives it Mosaic's default), HIGHEST
    # lowers to contract_precision<fp32>
    precision = jax.lax.Precision.HIGHEST

    return Semiring(
        name="plus_times",
        mul=np.multiply,
        add_reduceat=lambda v, s: np.add.reduceat(v, s),
        zero=0.0,
        jnp_matmul=lambda a, b: jnp.matmul(
            a, b, precision=precision, preferred_element_type=jnp.float32),
        jnp_add=lambda acc, c: acc + c,
        # the one true MXU fast path: a single f32-accumulating dot
        jnp_tile_combine=lambda acc, a, b: acc + jnp.dot(
            a, b, precision=precision, preferred_element_type=jnp.float32),
        jnp_segment_reduce=lambda v, seg, n: jax.ops.segment_sum(
            v, seg, num_segments=n),
        jnp_axis_reduce=lambda v, axis: jax.lax.psum(v, axis),
        dot_precision=precision,
    )


def _make_bool_or_and() -> Semiring:
    import jax
    import jax.numpy as jnp

    # 0/1 operands are exact in bfloat16: one pass suffices
    precision = jax.lax.Precision.DEFAULT

    # represent booleans as {0.0, 1.0}; or == max, and == min(prod on 0/1)
    def _bool_matmul(a, b):
        return jnp.clip(
            jnp.matmul((a != 0).astype(jnp.float32),
                       (b != 0).astype(jnp.float32), precision=precision,
                       preferred_element_type=jnp.float32), 0.0, 1.0)

    return Semiring(
        name="bool_or_and",
        mul=lambda a, b: (a != 0).astype(np.float64) * (b != 0),
        add_reduceat=lambda v, s: np.maximum.reduceat(v, s),
        zero=0.0,
        jnp_matmul=_bool_matmul,
        jnp_add=lambda acc, c: jnp.maximum(acc, c),
        # still MXU work: booleanize, dot, clip — then or==max into the acc
        jnp_tile_combine=lambda acc, a, b: jnp.maximum(acc, _bool_matmul(a, b)),
        jnp_segment_reduce=lambda v, seg, n: jax.ops.segment_max(
            v, seg, num_segments=n),
        jnp_axis_reduce=lambda v, axis: jax.lax.pmax(v, axis),
        dot_precision=precision,
    )


def _make_min_plus() -> Semiring:
    import jax
    import jax.numpy as jnp

    def _mp_matmul(a, b):
        # (i,k)+(k,j) min over k — tropical product of dense tiles.
        # Broadcast form: fine for the batched jnp reference engine on small
        # tiles; the Pallas kernel uses the rank-1 combine below to avoid
        # the O(bs^3) VMEM intermediate.
        return jnp.min(a[..., :, :, None] + b[..., None, :, :], axis=-2)

    def _mp_tile_combine(acc, a, b):
        # VPU formulation: stream rank-1 (column + row) updates, keeping
        # every intermediate at (bs, bs). The loop is unrolled over static
        # slices: the TPU kernel compiler has no lowering for a lane
        # dynamic_slice, which a fori_loop over k would need.
        for k in range(a.shape[-1]):
            acc = jnp.minimum(acc, a[:, k:k + 1] + b[k:k + 1, :])
        return acc

    return Semiring(
        name="min_plus",
        mul=np.add,
        add_reduceat=lambda v, s: np.minimum.reduceat(v, s),
        zero=float("inf"),
        jnp_matmul=_mp_matmul,
        jnp_add=lambda acc, c: jnp.minimum(acc, c),
        jnp_tile_combine=_mp_tile_combine,
        jnp_segment_reduce=lambda v, seg, n: jax.ops.segment_min(
            v, seg, num_segments=n),
        jnp_axis_reduce=lambda v, axis: jax.lax.pmin(v, axis),
    )


PLUS_TIMES = _make_plus_times()
BOOL_OR_AND = _make_bool_or_and()
MIN_PLUS = _make_min_plus()

_REGISTRY = {s.name: s for s in (PLUS_TIMES, BOOL_OR_AND, MIN_PLUS)}


def by_name(name: str) -> Semiring:
    return _REGISTRY[name]
