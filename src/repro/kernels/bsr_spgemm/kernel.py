"""Pallas TPU kernel: scheduled block-sparse matmul (local SpGEMM engine).

The hash/heap local SpGEMM of the paper probes scalar entries — there is no
MXU analogue. The TPU-native translation keeps the *sparsity* in a static,
host-built product schedule (see ``core/blocksparse.build_schedule``) and
makes every unit of work a dense ``bs×bs`` semiring tile-product:

    for s in range(nprod):            # one sequential Pallas grid
        C[c_slot[s]]  (+)=  A[a_slot[s]] ⊗ B[b_slot[s]]

The kernel is **semiring-generic** (ROADMAP "semiring contract"): the
accumulator resets to ``semiring.zero`` (the additive identity — not a
literal 0.0, which is the wrong annihilator for min-plus), and each step
applies ``semiring.jnp_tile_combine``. For plus-times that combine is one
f32-accumulating ``jnp.dot`` at ``Precision.HIGHEST`` (float32 operands in
several bfloat16 MXU passes, six assumed, not Mosaic's default single pass);
bool or-and stays on the MXU (booleanize → dot → clip → max); min-plus runs
``bs`` unrolled rank-1 ``min(acc, col + row)`` VPU updates over static
slices, so no O(bs³) intermediate is materialized.

The schedule arrays ride in via ``PrefetchScalarGridSpec`` so the BlockSpec
``index_map``s can address the right payload tile of A/B/C *before* the body
runs (scalar prefetch is how Pallas TPU does data-dependent tiling). Because
the schedule is sorted by output slot, each output tile's products are
contiguous: the accumulator lives in a VMEM scratch, is reset on the first
visit, and is flushed on the last — output payloads are written exactly once
(revisit-free). Output slots no product targets are never written and hold
unspecified payloads; callers that pad a schedule to a static length point
the pad products at a trailing garbage slot (with valid payload slots and
flags from ``blocksparse.flags_from_c_slot``) and drop it afterwards — this
is how the distributed ring (``core/spgemm_1d_device.py``) runs its
per-device schedules over the combined post-fetch stack, mask-free.

VMEM budget per step: 3 payload tiles (A, B in, C out) + 1 f32 accumulator.
At bs=128, f32: 4 × 64 KiB = 256 KiB — far under ~16 MiB/core VMEM, so the
pipeline runs double-buffered and consecutive products on the same A (or B)
payload skip the redundant DMA (Pallas revisiting elision).

SMEM budget: the four prefetched schedule arrays cost 16 B per product, and
a v5e core has 1 MiB of SMEM, so one launch over a whole schedule stops
compiling near 65k products (a real hv15r-like A² schedules ~367k). The
schedule therefore runs in **windows** of at most ``SCHEDULE_WINDOW``
products, one ``pallas_call`` each, and each call is handed only its own
static slice of the schedule arrays. All windows write one output buffer:
every window after the first takes the previous window's output as an
aliased input (``input_output_aliases``), so tiles it does not visit keep
their values. Windows are cut at fixed offsets, not at output-tile
boundaries — that keeps the static window geometry identical on every
device of a shard_map body, whose schedules differ. An output tile whose run
of products straddles a cut is flushed as a partial at the window's last
step and read back from HBM into the accumulator at the next window's first
step (its flags there carry no first-visit bit), so each window still
writes each of its output tiles exactly once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.semiring import PLUS_TIMES, Semiring
from ..launch import launch

__all__ = ["bsr_spgemm_pallas"]


# Products per launch. 16 B/product puts a window's prefetched schedule at
# 256 KiB, a quarter of a v5e core's 1 MiB SMEM, which leaves the compiler
# its own scalar memory; each extra launch costs one pipeline fill, under
# 1% of a window's ~16k grid steps.
SCHEDULE_WINDOW = 16384


def _kernel(
    # ---- scalar-prefetch operands (SMEM): this window's schedule ----
    a_slot,      # (n,) i32 payload index into a_tiles
    b_slot,      # (n,) i32 payload index into b_tiles
    c_slot,      # (n,) i32 payload index into c_tiles
    flags,       # (n,) i32 bit0: first visit, bit1: last visit
    # ---- array operands ----
    a_ref,       # (bs, bs) current A payload (VMEM block)
    b_ref,       # (bs, bs) current B payload (VMEM block)
    *refs,       # [c_prev (nc, bs, bs) in HBM, aliased to the output],
                 # c_ref (bs, bs) current C payload, acc_ref f32 scratch
    semiring: Semiring,
    n: int,
    resume: bool,
):
    if resume:
        c_prev, c_ref, acc_ref = refs
    else:
        c_ref, acc_ref = refs
    s = pl.program_id(0)
    first = (flags[s] & 1) != 0
    # the window's last step flushes whatever it holds: a tile whose run
    # continues past the cut is written as a partial and resumed below
    last = ((flags[s] & 2) != 0) | (s == n - 1)

    @pl.when(first)
    def _reset():
        # additive identity, NOT literal zeros (min-plus resets to +inf)
        acc_ref[...] = jnp.full_like(acc_ref, semiring.zero)

    if resume:
        @pl.when((s == 0) & jnp.logical_not(first))
        def _resume():
            pltpu.sync_copy(c_prev.at[c_slot[s]], acc_ref)

    acc_ref[...] = semiring.jnp_tile_combine(
        acc_ref[...], a_ref[...], b_ref[...])

    @pl.when(last)
    def _flush():
        c_ref[...] = acc_ref[...]


def _launch_window(a_tiles, b_tiles, a_slot, b_slot, c_slot, flags, c_prev,
                   *, nc: int, bs: int, interpret: Optional[bool],
                   semiring: Semiring):
    """One ``pallas_call`` over one window's schedule slice."""
    n = a_slot.shape[0]
    resume = c_prev is not None

    def tile(which):
        # index_map signature: (grid_idx, *prefetch_refs); ``which`` picks
        # the a_slot / b_slot / c_slot array
        return pl.BlockSpec((None, bs, bs),
                            lambda s, *sched: (sched[which][s], 0, 0))

    in_specs = [tile(0), tile(1)]
    operands = [a_slot, b_slot, c_slot, flags, a_tiles, b_tiles]
    aliases = {}
    if resume:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(c_prev)
        aliases = {len(operands) - 1: 0}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n,),
        in_specs=in_specs,
        out_specs=tile(2),
        scratch_shapes=[pltpu.VMEM((bs, bs), jnp.float32)],
    )
    return launch(
        functools.partial(_kernel, semiring=semiring, n=n, resume=resume),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nc, bs, bs), jnp.float32),
        interpret=interpret,
        # products that hit the same output tile must execute in order
        dimension_semantics=("arbitrary",),
        input_output_aliases=aliases,
        # the launch's op name in a compiled program and its device trace
        # (``bsr_spgemm_pallas.<k>``), whatever jitted function encloses it
        name="bsr_spgemm_pallas",
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=("nprod", "nc", "bs", "interpret", "semiring",
                     "seg_start", "window"))
def bsr_spgemm_pallas(a_tiles, b_tiles, a_slot, b_slot, c_slot, flags,
                      *, nprod: int, nc: int, bs: int,
                      interpret: Optional[bool] = None,
                      semiring: Semiring = PLUS_TIMES, seg_start: int = 0,
                      window: int = SCHEDULE_WINDOW):
    """Run the product schedule; returns (nc, bs, bs) f32 output payloads.

    a_tiles / b_tiles : (na, bs, bs), (nb, bs, bs) payload stacks whose
        absent positions hold ``semiring.zero``
    a_slot/b_slot/c_slot/flags : (nprod,)-or-longer i32 schedule. Contents
        are traced data (scalar-prefetched); only lengths are static.
    semiring : static; supplies the accumulator identity and the per-step
        tile combine (plus-times keeps the single-``jnp.dot`` MXU path).
    seg_start : static segment offset — execute products
        ``[seg_start, seg_start + nprod)`` of the schedule arrays. The
        chunked 1D ring streams one contiguous schedule segment per
        payload chunk out of the same flat arrays.
    window : products per ``pallas_call`` (see the module docstring's SMEM
        budget); the default is the one every engine uses.
    """
    if nprod == 0:
        # an empty schedule's output is all additive identities — for
        # min-plus that decodes to "empty", not to a dense block of zeros
        return jnp.full((max(nc, 1), bs, bs), semiring.zero,
                        dtype=jnp.float32)

    out = None
    for lo in range(seg_start, seg_start + nprod, window):
        hi = min(lo + window, seg_start + nprod)
        out = _launch_window(
            a_tiles, b_tiles, *(x[lo:hi] for x in (a_slot, b_slot, c_slot,
                                                   flags)),
            out, nc=nc, bs=bs, interpret=interpret, semiring=semiring)
    return out
