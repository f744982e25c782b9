"""Pure-jnp oracle for the scheduled block-sparse matmul kernel."""

from __future__ import annotations

import jax.numpy as jnp

from ...core.semiring import PLUS_TIMES, Semiring

__all__ = ["bsr_spgemm_ref"]


def bsr_spgemm_ref(a_tiles, b_tiles, a_slot, b_slot, c_slot,
                   *, nc: int, semiring: Semiring = PLUS_TIMES,
                   seg_start: int = 0, seg_len: int = None):
    """Segment-reduce formulation of the same schedule.

    C[c_slot[s]] (+)= A[a_slot[s]] ⊗ B[b_slot[s]]  for every product s,
    over the additive monoid of ``semiring``.

    Unlike the Pallas kernel this materializes all ``nprod`` padded
    products at once (O(nprod·bs²) intermediate) — it is the reference
    engine, not the product path. Padded schedules follow the same
    garbage-slot convention (pads target slot ``nc-1``, dropped by the
    caller). ``seg_start``/``seg_len`` mirror the Pallas kernel's static
    segment-offset launch: only products ``[seg_start, seg_start+seg_len)``
    execute (the chunked ring streams one schedule segment per payload
    chunk). Unscheduled segments come back as the identity of the
    underlying jax segment reduce (0 for segment_sum, ±inf for
    segment_min/max) — unspecified from the kernel; ring callers mask
    them to ``semiring.zero`` before decoding either way.
    """
    bs = a_tiles.shape[-1]
    if seg_len is None:
        seg_len = len(a_slot) - seg_start
    a_slot = a_slot[seg_start:seg_start + seg_len]
    b_slot = b_slot[seg_start:seg_start + seg_len]
    c_slot = c_slot[seg_start:seg_start + seg_len]
    if len(a_slot) == 0:
        return jnp.full((max(nc, 1), bs, bs), semiring.zero,
                        dtype=jnp.float32)
    prods = semiring.jnp_matmul(
        a_tiles[a_slot].astype(jnp.float32),
        b_tiles[b_slot].astype(jnp.float32),
    )
    return semiring.jnp_segment_reduce(prods, c_slot, nc)
