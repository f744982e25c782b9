"""Device execution of the sparsity-aware 1D SpGEMM — shard_map ring.

This is the TPU translation of Algorithm 1's numeric phase. The MPI original
issues passive-target ``MPI_Get``s against remote windows; XLA has no
one-sided runtime fetch, so the *planned* transfers are realized as a ring
of ``ppermute`` steps inside ``shard_map``:

    step s ∈ {1..P-1}: device j packs the payload tiles that device
    (j-s) mod P 's plan requests from it, and one collective-permute with
    shift -s delivers every pair at distance s simultaneously.

Everything data-dependent is resolved on the host *before* tracing, from the
same sparsity metadata the MPI version allgathers (tile-level DCSC: nonzero
tile-column ids per owner). What remains on device is static-shaped:

  * payload stacks padded to the per-step maximum over pairs (the padded
    bytes are reported next to the exact planned bytes — the price of
    static shapes is visible, not hidden);
  * a per-device product schedule (see ``blocksparse.build_schedule``)
    over the combined post-fetch stack (own tiles ++ per-step receives),
    executed by the revisit-free Pallas bsr kernel: products are streamed
    in output-slot order, a VMEM accumulator is reset on each first visit
    and flushed on each last visit, so no O(nprod·bs²) intermediate is ever
    materialized. Schedule pad entries point at payload slot 0 and at a
    trailing garbage output slot that is dropped after the call, which
    keeps both engines mask-free. The ``jnp`` segment-sum formulation of
    the same schedule is retained as a selectable reference engine
    (``engine="jnp"``); ``engine="auto"`` resolves to the Pallas kernel,
    which CPU CI exercises through interpret mode
    (``launch.resolve_interpret``).

The whole path is **semiring-generic** (ROADMAP "semiring contract"): the
plan is built for one :class:`~repro.core.semiring.Semiring`, whose additive
identity fills every absent tile position, pad payload slot and pad product,
and whose ``prune_mask`` drives the output decode — no layer ever assumes
the identity is a literal ``0.0``. That is what lets the betweenness-
centrality (bool or-and) and shortest-path (min-plus) workloads of §II.C
run on the same ring/kernel as plus-times.

The paper's block-fetch strategy (Algorithm 2) appears here twice: the tile
side length ``bs`` is the fetch granularity (a tile column is fetched iff it
intersects a required element column), and ``nblocks`` optionally coarsens
further by grouping tile-columns, bounding per-pair fragment counts exactly
like the paper bounds RDMA message counts.

Planner invariant: plan construction contains **no Python-level per-tile
loops** — payload needs, block-fetch grouping, product schedules, and the
output decode are all computed with array ops (see ROADMAP.md). Loops over
devices / ring steps (O(P), O(P²) with vectorized bodies) are fine; loops
over tiles or nonzeros are not.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compat import cpu_device_mesh, shard_map
from ..runtime.spans import span
from .blocksparse import BlockSparse, build_schedule, flags_from_c_slot
from .device_common import (ENGINES, DecodeMap, blockize_parts,
                            check_plan_semiring, decode_tiles,
                            pack_schedules, resolve_engine, run_schedule,
                            snap_to_tiles)
from .plan import BYTES_PER_NNZ, Partition1D
from .semiring import PLUS_TIMES, Semiring
from .sparse import CSC

__all__ = ["DeviceSpGEMMPlan", "build_device_plan", "compile_ring",
           "ring_program", "ring_args", "run_device_spgemm",
           "decode_ring_output", "payload_need_maps", "repack_ring_payloads",
           "segment_ring_schedule", "slot_map", "refill_ring_stacks",
           "ring_values", "RingValueScatter", "compile_ring_scatter",
           "ring_decode_map", "ENGINES"]

# a values-only repack scatters through int32 flat indices into one
# device's payload stack, so the stack plus its pad indices must stay
# below this; a larger plan keeps the host refill
POS_LIMIT = 2 ** 31


# ---------------------------------------------------------------------------
# host-side plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceSpGEMMPlan:
    """Static-shape plan for one distributed device SpGEMM call."""

    nparts: int
    bs: int
    # padded per-device stacks (numpy, to be device_put sharded):
    a_tiles: np.ndarray        # (P, na_max, bs, bs)
    b_tiles: np.ndarray        # (P, nb_max, bs, bs)
    send_slots: np.ndarray     # (P, S_total) i32: per-step packed slot ids, -1 pad
    # per-device product schedule over the post-fetch combined stack
    # (pad products: a_slot/b_slot 0, c_slot nc_max — the garbage slot):
    a_slot: np.ndarray         # (P, nprod_max) i32
    b_slot: np.ndarray         # (P, nprod_max) i32
    c_slot: np.ndarray         # (P, nprod_max) i32
    flags: np.ndarray          # (P, nprod_max) i32 bit0 first / bit1 last visit
    # static step geometry:
    step_sizes: Tuple[int, ...]   # max payload count per ring step (len P-1)
    nc_max: int
    # decode info (host): output tile coords per device, 0-padded past counts
    c_rows: np.ndarray         # (P, nc_max) i32
    c_cols: np.ndarray         # (P, nc_max) i32
    c_counts: np.ndarray       # (P,) real output-tile count per device
    part_k: Partition1D        # tile-snapped contraction partition (A cols)
    part_n: Partition1D
    out_shape: Tuple[int, int]
    # the semiring the payloads were built for: every pad above is filled
    # with its additive identity, and the decode prunes against it
    semiring: Semiring
    # accounting:
    exact_bytes: int           # planned payload bytes (sum of real tiles moved)
    padded_bytes: int          # what the static-shape ring actually moves
    stats: dict
    # ---- chunked double-buffered pipeline (chunk=None: legacy single-pass
    # ring — fetch everything, one schedule launch). chunk=c splits the
    # ring steps into groups of <= c consecutive steps; the shard_map body
    # issues group g+1's ppermutes into the spare payload slot while group
    # g's schedule segment streams through the kernel, and per-segment
    # partials combine under the semiring's additive monoid. The schedule
    # arrays above are then flat per-segment blocks addressed by the
    # static (seg_prod_off, seg_prod_len) pairs, with a_slot local to each
    # segment's payload stack (own tiles for segment 0, the group's
    # concatenated receives otherwise).
    chunk: Optional[int] = None
    seg_steps: Tuple[Tuple[int, ...], ...] = ((),)   # ring steps per segment
    seg_payload_sizes: Tuple[int, ...] = (0,)        # payload tiles per segment
    seg_prod_off: Tuple[int, ...] = (0,)             # flat schedule offsets
    seg_prod_len: Tuple[int, ...] = (0,)             # padded products per seg
    # values-only repack on the device (see slot_map): the ascending flat
    # positions of A's / B's stored entries in each device's payload stack
    # and the CSC data index of the entry at each; None where int32
    # cannot address the stack
    a_pos: Optional[np.ndarray] = None    # (P, nnz_a_max) i32
    a_order: Optional[np.ndarray] = None  # (P, nnz_a_max) i32
    b_pos: Optional[np.ndarray] = None    # (P, nnz_b_max) i32
    b_order: Optional[np.ndarray] = None  # (P, nnz_b_max) i32
    # the structural decode map (see ring_decode_map), host memory; set
    # by the session on the plan's second use, None until then
    decode_map: Optional[DecodeMap] = None


def slot_map(parts: List[BlockSparse], stack_shape: Tuple[int, ...]
             ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Where each part's stored entries sit in its device's payload stack
    (``stack_shape[1:]``): ``(pos, order)``, each ``(P, nnz_max)`` int32.
    ``pos`` holds the flat positions (``from_csc``'s ``entry_pos``) in
    ascending order, padded to the longest part with distinct ascending
    out-of-range indices that a scatter drops; ``order`` the index in the
    part's CSC data of the entry at each position.

    The structure fixes every position, so new values on the same
    structure refill the stack by one scatter of ``data[order]`` through
    ``pos``. Ascending indices matter on the TPU: unsorted ones put a sort
    of the map into the scatter's program, and about 20 s into its
    compile. Returns None where the stack and its pads exceed
    :data:`POS_LIMIT`."""
    size = int(np.prod(stack_shape[1:]))
    counts = [len(p.entry_pos) for p in parts]
    width = max(max(counts, default=0), 1)
    if size + width > POS_LIMIT:
        return None
    pos = np.tile(size + np.arange(width, dtype=np.int64), (len(parts), 1))
    order = np.tile(np.arange(width, dtype=np.int64), (len(parts), 1))
    for j, p in enumerate(parts):
        o = np.argsort(p.entry_pos, kind="stable")
        pos[j, :counts[j]] = p.entry_pos[o]
        order[j, :counts[j]] = o
    return pos.astype(np.int32), order.astype(np.int32)


def payload_need_maps(a_parts: List[BlockSparse],
                      col_tile_off: List[int],
                      hit: np.ndarray,
                      nblocks: Optional[int]) -> List[np.ndarray]:
    """Per-owner payload-need matrices, one array op pass per owner.

    Returns, for each owner ``src``, a ``(P, ntiles_src)`` bool matrix whose
    row ``dst`` marks the tiles of ``A_src`` that ``dst``'s plan fetches:
    tile t is needed iff its global tile-col is hit by ``H_dst`` —
    optionally coarsened by the Algorithm-2 ``nblocks`` grouping (the
    owner's distinct nonzero tile-cols are cut into ≤ nblocks groups and
    whole groups are fetched). The grouping is computed once per owner and
    applied to every destination at once; there is no per-tile Python loop
    and no per-(src, dst) dict rebuild.
    """
    Pn = hit.shape[0]
    need_all: List[np.ndarray] = []
    for src, ap in enumerate(a_parts):
        if not ap.ntiles:
            need_all.append(np.zeros((Pn, 0), dtype=bool))
            continue
        gcols = ap.tile_cols + col_tile_off[src]
        need = hit[:, gcols]                       # (P, ntiles_src)
        if nblocks is not None:
            nz = np.unique(ap.tile_cols)
            k = min(nblocks, len(nz))
            bounds = np.linspace(0, len(nz), k + 1).astype(np.int64)
            grp_of_nz = np.searchsorted(bounds, np.arange(len(nz)),
                                        side="right") - 1
            # tile_cols is sorted (from_csc orders by (col, row)), so the
            # per-tile group ids are nondecreasing and each group is one
            # contiguous run — a single reduceat ORs every run per dst.
            grp_of_tile = grp_of_nz[np.searchsorted(nz, ap.tile_cols)]
            starts = np.searchsorted(grp_of_tile, np.arange(k), side="left")
            grp_hit = np.bitwise_or.reduceat(need, starts, axis=1)
            need = grp_hit[:, grp_of_tile]
        need_all.append(need)
    return need_all


def segment_ring_schedule(scheds: List[dict], step_sizes: Sequence[int],
                          max_na: int, chunk: int, nc_max: int) -> dict:
    """Split per-device combined-stack schedules into per-chunk segments.

    ``scheds[d]`` carries the device's products over the combined
    post-fetch stack (``a_slot`` in combined-stack coordinates, ``c_slot``
    nondecreasing). The ring steps are grouped into runs of ``<= chunk``
    consecutive steps; segment 0 is the resident own-tile stack, segment
    ``1+g`` is receive group ``g``. Products are routed to the segment
    whose payload region their ``a_slot`` falls in (one vectorized
    ``searchsorted`` per device — the combined layout is contiguous per
    group, so the rebase to segment-local payload indices is a subtraction)
    and packed into per-segment ``(P, len_g)`` blocks concatenated flat,
    with pads pointing at local payload slot 0 and the garbage output slot
    ``nc_max``. Product order is preserved inside each segment, so each
    segment's ``c_slot`` stays nondecreasing and its first/last-visit
    flags are valid *within the segment*; cross-segment revisits are
    combined by the pipeline body under the semiring's additive monoid.
    """
    Pn = len(scheds)
    nsteps = len(step_sizes)
    step_off = np.concatenate(
        [[0], np.cumsum(np.asarray(step_sizes, dtype=np.int64))])
    groups = [tuple(range(g, min(g + chunk, nsteps)))
              for g in range(0, nsteps, chunk)]
    # payload region starts in the combined stack, one per segment
    seg_payload_off = np.asarray(
        [0] + [max_na + int(step_off[g[0]]) for g in groups], dtype=np.int64)
    seg_payload_sizes = tuple(
        [max_na] + [int(step_off[g[-1] + 1] - step_off[g[0]])
                    for g in groups])
    G = len(seg_payload_off)

    parts: List[List[Tuple[np.ndarray, np.ndarray, np.ndarray]]] = []
    counts = np.zeros((Pn, G), dtype=np.int64)
    for d, s in enumerate(scheds):
        a_sl = np.asarray(s["a_slot"], dtype=np.int64)
        sid = np.searchsorted(seg_payload_off, a_sl, side="right") - 1
        row = []
        for g in range(G):
            m = sid == g
            row.append((a_sl[m] - seg_payload_off[g],
                        np.asarray(s["b_slot"])[m],
                        np.asarray(s["c_slot"])[m]))
            counts[d, g] = int(m.sum())
        parts.append(row)

    seg_len = tuple(int(x) for x in counts.max(axis=0))
    seg_off = tuple(int(x) for x in
                    np.concatenate([[0], np.cumsum(seg_len)[:-1]]))
    total = max(int(sum(seg_len)), 1)
    A = np.zeros((Pn, total), dtype=np.int32)
    B = np.zeros((Pn, total), dtype=np.int32)
    C = np.full((Pn, total), nc_max, dtype=np.int32)
    for d in range(Pn):
        for g in range(G):
            al, bl, cl = parts[d][g]
            o = seg_off[g]
            A[d, o:o + len(al)] = al
            B[d, o:o + len(bl)] = bl
            C[d, o:o + len(cl)] = cl
    # flags are per-segment: each (P, len_g) block gets its own
    # first/last-visit runs (pads form a trailing garbage-slot run)
    F = np.zeros((Pn, total), dtype=np.int32)
    for g in range(G):
        o, ln = seg_off[g], seg_len[g]
        if ln:
            F[:, o:o + ln] = flags_from_c_slot(C[:, o:o + ln])
    return dict(a_slot=A, b_slot=B, c_slot=C, flags=F,
                seg_steps=((),) + tuple(groups),
                seg_payload_sizes=seg_payload_sizes,
                seg_prod_off=seg_off, seg_prod_len=seg_len)


def build_device_plan(a: CSC, b: CSC, nparts: int,
                      part_k: Optional[Partition1D] = None,
                      part_n: Optional[Partition1D] = None,
                      bs: int = 128,
                      nblocks: Optional[int] = None,
                      dtype=np.float32,
                      semiring: Semiring = PLUS_TIMES,
                      a_blockize_cache: Optional[dict] = None,
                      chunk: Optional[int] = None
                      ) -> DeviceSpGEMMPlan:
    """Symbolic phase at tile granularity + static-shape padding.

    ``semiring`` fixes the payload fill: every absent tile position, pad
    slot and pad product is the semiring's additive identity (its
    multiplicative annihilator too), so the engines stay mask-free under
    min-plus / bool exactly as under plus-times.

    ``chunk`` enables the double-buffered k-chunk pipeline: the ring steps
    are grouped into runs of ``<= chunk`` steps, the product schedule is
    split into matching segments at build time, and the compiled body
    overlaps each group's fetch with the previous segment's compute,
    bounding the per-device fetched working set by two adjacent chunks
    instead of the whole gathered stack. ``None`` keeps the legacy
    single-pass ring. Both decode bitwise-identically for every semiring.

    ``a_blockize_cache``: callers that re-plan against the *same* A many
    times (BC multiplies one adjacency operand by a fresh frontier every
    level) pass a dict here to reuse A's blockization across calls. The
    cache pins the operand object (so the ``id``-based key cannot go
    stale) and assumes it is not mutated between calls.
    """
    assert a.ncols == b.nrows
    if chunk is not None:
        chunk = int(chunk)
        if chunk < 1:
            raise ValueError(f"chunk must be a positive int or None, "
                             f"got {chunk}")
    t_plan0 = time.perf_counter()
    Pn = nparts
    if part_k is None:
        part_k = Partition1D.balanced(a.ncols, Pn)
    if part_n is None:
        part_n = Partition1D.balanced(b.ncols, Pn)
    # the k partition must land on tile boundaries, otherwise the parts'
    # local tile grids don't embed into the global k tile space
    part_k = snap_to_tiles(part_k, bs)

    if a_blockize_cache is None:
        a_parts = blockize_parts(a, part_k, bs, dtype, fill=semiring.zero)
    else:
        key = (id(a), tuple(int(s) for s in part_k.splits), bs,
               np.dtype(dtype).str, float(semiring.zero))
        cached = a_blockize_cache.get(key)
        if cached is None or cached[0] is not a:
            cached = (a, blockize_parts(a, part_k, bs, dtype,
                                         fill=semiring.zero))
            # bounded FIFO: callers alternate between a handful of static
            # operands (BC: Aᵀ forward / A backward); evicting beyond that
            # keeps the pinned-operand retention O(1), not O(calls)
            while len(a_blockize_cache) >= 4:
                a_blockize_cache.pop(next(iter(a_blockize_cache)))
            a_blockize_cache[key] = cached
        a_parts = cached[1]
    b_parts = blockize_parts(b, part_n, bs, dtype, fill=semiring.zero)

    # tile-level hit vectors: device i needs global tile-row g of B_i ⇔ some
    # nonzero of B_i falls in element rows [g*bs, (g+1)*bs)
    kg = math.ceil(a.ncols / bs)  # global tile count along k
    hit = np.zeros((Pn, kg), dtype=bool)
    for i, bp in enumerate(b_parts):
        hit[i, bp.tile_rows] = True

    # per-owner global tile-col offsets of A's local grids
    col_tile_off = [part_k.part_slice(j)[0] // bs for j in range(Pn)]

    need_all = payload_need_maps(a_parts, col_tile_off, hit, nblocks)

    # ring steps: at step s, dst i receives from src (i+s) mod P
    step_sizes: List[int] = []
    send_per_step: List[List[np.ndarray]] = []   # [step][device j] slots
    recv_per_dev: List[List[np.ndarray]] = [[] for _ in range(Pn)]
    exact_tiles = 0
    planned_msgs = 0
    for s in range(1, Pn):
        sends = []
        for j in range(Pn):
            dst = (j - s) % Pn
            slots = np.nonzero(need_all[j][dst])[0].astype(np.int32)
            sends.append(slots)
            exact_tiles += len(slots)
            planned_msgs += int(len(slots) > 0)
        step_sizes.append(max((len(sl) for sl in sends), default=0))
        send_per_step.append(sends)
        for i in range(Pn):
            recv_per_dev[i].append(sends[(i + s) % Pn])

    na_max = max((p.ntiles for p in a_parts), default=0)
    nb_max = max((p.ntiles for p in b_parts), default=0)
    S_total = sum(step_sizes)

    # pad slots hold the additive identity, not literal zeros (semiring fill)
    a_tiles = semiring.fill((Pn, max(na_max, 1), bs, bs), dtype=dtype)
    b_tiles = semiring.fill((Pn, max(nb_max, 1), bs, bs), dtype=dtype)
    send_slots = np.full((Pn, max(S_total, 1)), -1, dtype=np.int32)
    for j in range(Pn):
        if a_parts[j].ntiles:
            a_tiles[j, :a_parts[j].ntiles] = a_parts[j].tiles
        if b_parts[j].ntiles:
            b_tiles[j, :b_parts[j].ntiles] = b_parts[j].tiles
        off = 0
        for s_idx, mx in enumerate(step_sizes):
            sl = send_per_step[s_idx][j]
            send_slots[j, off:off + len(sl)] = sl
            off += mx

    # ---- per-device product schedule over the combined stack ---------------
    # combined stack layout on device i: [own A_i (na_max)] ++ recv step 1
    # (step_sizes[0]) ++ ... ++ recv step P-1. Build a BlockSparse "virtual"
    # A-view per device with *global* tile cols and stack-slot payload ids.
    max_na = max(na_max, 1)
    scheds = []
    for i in range(Pn):
        rows_l, cols_l, slots_l = [], [], []
        ap = a_parts[i]
        if ap.ntiles:
            rows_l.append(ap.tile_rows)
            cols_l.append(ap.tile_cols + col_tile_off[i])
            slots_l.append(np.arange(ap.ntiles, dtype=np.int64))
        off = max_na
        for s_idx in range(Pn - 1):
            src = (i + 1 + s_idx) % Pn
            slots = recv_per_dev[i][s_idx]
            spart = a_parts[src]
            if len(slots):
                rows_l.append(spart.tile_rows[slots])
                cols_l.append(spart.tile_cols[slots] + col_tile_off[src])
                slots_l.append(off + np.arange(len(slots), dtype=np.int64))
            off += step_sizes[s_idx]
        if rows_l:
            vrows = np.concatenate(rows_l).astype(np.int32)
            vcols = np.concatenate(cols_l).astype(np.int32)
            vslots = np.concatenate(slots_l)
        else:
            vrows = np.zeros(0, np.int32)
            vcols = np.zeros(0, np.int32)
            vslots = np.zeros(0, np.int64)

        # virtual A view (payloads indexed by stack slot), global k tile space
        virt = BlockSparse(
            tiles=np.zeros(  # replint: off=RS003 1x1 placeholder payloads; only tile coords feed build_schedule, values never read
                (len(vrows), 1, 1), dtype=dtype),
            tile_rows=vrows, tile_cols=vcols,
            shape=(a_parts[i].shape[0], kg * bs),
            orig_shape=(a.nrows, a.ncols), bs=bs)
        bp = b_parts[i]
        bview = BlockSparse(
            tiles=np.zeros(  # replint: off=RS003 1x1 placeholder payloads; only tile coords feed build_schedule, values never read
                (bp.ntiles, 1, 1), dtype=dtype),
            tile_rows=bp.tile_rows, tile_cols=bp.tile_cols,
            shape=(kg * bs, bp.shape[1]),
            orig_shape=(a.ncols, bp.orig_shape[1]), bs=bs)
        sched = build_schedule(virt, bview)
        scheds.append(dict(a_slot=vslots[sched.a_slot].astype(np.int32),
                           b_slot=sched.b_slot, c_slot=sched.c_slot,
                           c_rows=sched.c_rows, c_cols=sched.c_cols))

    # pad products target the garbage output slot nc_max with payload slot 0:
    # the engines compute them unmasked and the trailing slot is dropped.
    packed = pack_schedules(scheds)
    nprod_max, nc_max = packed["nprod_max"], packed["nc_max"]

    # ---- schedule segmentation (chunked pipeline) --------------------------
    if chunk is None:
        # legacy single-pass ring: one segment spanning own + all receives
        sched_flat = dict(a_slot=packed["a_slot"], b_slot=packed["b_slot"],
                          c_slot=packed["c_slot"], flags=packed["flags"])
        seg_steps: Tuple[Tuple[int, ...], ...] = (tuple(range(Pn - 1)),)
        seg_payload_sizes = (max_na + S_total,)
        seg_prod_off = (0,)
        seg_prod_len = (int(nprod_max),)
        peak_payload_tiles = max_na + S_total
        overlap_fraction = 0.0
    else:
        seg = segment_ring_schedule(scheds, step_sizes, max_na, chunk,
                                    nc_max)
        sched_flat = dict(a_slot=seg["a_slot"], b_slot=seg["b_slot"],
                          c_slot=seg["c_slot"], flags=seg["flags"])
        seg_steps = seg["seg_steps"]
        seg_payload_sizes = seg["seg_payload_sizes"]
        seg_prod_off = seg["seg_prod_off"]
        seg_prod_len = seg["seg_prod_len"]
        # double-buffered working set: own stack + current + next chunk
        rs = list(seg_payload_sizes[1:])
        if not rs:
            peak_payload_tiles = max_na
        elif len(rs) == 1:
            peak_payload_tiles = max_na + rs[0]
        else:
            peak_payload_tiles = max_na + max(
                rs[i] + rs[i + 1] for i in range(len(rs) - 1))
        # modeled fetch-issue overlap: a chunk's fetch is overlapped iff
        # the preceding segment has compute to hide it behind
        overlapped = sum(rs[i] for i in range(len(rs))
                         if seg_prod_len[i] > 0)
        overlap_fraction = overlapped / S_total if S_total else 0.0

    tile_bytes = bs * bs * np.dtype(dtype).itemsize
    padded_tiles = Pn * S_total
    nprod_total = int(sum(len(s["a_slot"]) for s in scheds))
    a_pos, a_order = slot_map(a_parts, a_tiles.shape) or (None, None)
    b_pos, b_order = slot_map(b_parts, b_tiles.shape) or (None, None)
    plan_seconds = time.perf_counter() - t_plan0
    return DeviceSpGEMMPlan(
        nparts=Pn, bs=bs,
        a_tiles=a_tiles, b_tiles=b_tiles, send_slots=send_slots,
        a_slot=sched_flat["a_slot"], b_slot=sched_flat["b_slot"],
        c_slot=sched_flat["c_slot"], flags=sched_flat["flags"],
        step_sizes=tuple(step_sizes), nc_max=nc_max,
        c_rows=packed["c_rows"], c_cols=packed["c_cols"],
        c_counts=packed["c_counts"],
        part_k=part_k, part_n=part_n, out_shape=(a.nrows, b.ncols),
        semiring=semiring,
        exact_bytes=exact_tiles * tile_bytes,
        padded_bytes=padded_tiles * tile_bytes,
        chunk=chunk, seg_steps=seg_steps,
        seg_payload_sizes=seg_payload_sizes,
        seg_prod_off=seg_prod_off, seg_prod_len=seg_prod_len,
        a_pos=a_pos, a_order=a_order, b_pos=b_pos, b_order=b_order,
        stats=dict(
            # shared device-engine stats surface (device_common.REQUIRED_STATS)
            comm_bytes_planned=exact_tiles * tile_bytes,
            comm_bytes_padded=padded_tiles * tile_bytes,
            messages=int(planned_msgs),
            dense_flops=2 * nprod_total * bs ** 3,
            plan_seconds=plan_seconds,
            peak_payload_tiles=int(peak_payload_tiles),
            chunks=len(seg_steps),
            overlap_fraction=float(overlap_fraction),
            # 1D-specific detail
            na_max=na_max, nb_max=nb_max, nprod_max=int(nprod_max),
            nprod_total=nprod_total,
            nc_max=int(nc_max), ring_steps=Pn - 1,
            exact_tiles=int(exact_tiles), padded_tiles=int(padded_tiles),
        ),
    )


def _refill_stack(mat: CSC, part: Partition1D, shape, bs: int, dtype,
                  semiring: Semiring) -> np.ndarray:
    parts = blockize_parts(mat, part, bs, dtype, fill=semiring.zero)
    stack = semiring.fill(shape, dtype=dtype)
    for j, p in enumerate(parts):
        if p.ntiles:
            stack[j, :p.ntiles] = p.tiles
    return stack


def refill_ring_stacks(plan: DeviceSpGEMMPlan,
                       a: Optional[CSC] = None,
                       b: Optional[CSC] = None
                       ) -> Tuple[Optional[np.ndarray], Optional[np.ndarray]]:
    """Fresh payload stacks for *structure-identical* operands, on the host.

    The values-only half of re-planning: blockize the changed operand(s)
    on the plan's (tile-snapped) partitions and refill the static payload
    stacks. Pass only the side(s) whose values changed — a ``None``
    operand returns a ``None`` stack, so a loop-invariant operand (BC's
    adjacency across the backward sweep) costs nothing to keep resident.
    Everything structural — schedules, send slots, step geometry, decode
    coordinates — is untouched, so the caller can reuse the plan and its
    compiled executable. Blockization is deterministic given structure
    (``from_csc`` orders tiles by (col, row)), so feeding these stacks to
    the cached executable decodes bitwise-identically to a cold re-plan.
    The reference for :class:`RingValueScatter`, which builds the same
    stacks on the device.
    """
    dtype = plan.a_tiles.dtype
    sr = plan.semiring
    a_tiles = None if a is None else _refill_stack(
        a, plan.part_k, plan.a_tiles.shape, plan.bs, dtype, sr)
    b_tiles = None if b is None else _refill_stack(
        b, plan.part_n, plan.b_tiles.shape, plan.bs, dtype, sr)
    return a_tiles, b_tiles


def ring_values(plan: DeviceSpGEMMPlan, side: int, mat: CSC) -> np.ndarray:
    """``(P, width)`` new values of ``mat`` as side 0 (A) or 1 (B) of the
    plan, in the order of its slot map: each ring part is a column range,
    so its values are one contiguous slice of ``data``, taken in the
    map's order, cast to the payload dtype and padded to the map's width
    (the scatter drops the pads)."""
    order = plan.a_order if side == 0 else plan.b_order
    part = plan.part_k if side == 0 else plan.part_n
    splits = part.splits.astype(np.int64)
    lo, hi = mat.indptr[splits[:-1]], mat.indptr[splits[1:]]
    out = np.full(order.shape, plan.semiring.zero, dtype=plan.a_tiles.dtype)
    for j in range(len(lo)):
        n = hi[j] - lo[j]
        out[j, :n] = mat.data[lo[j]:hi[j]][order[j, :n]]
    return out


def repack_ring_payloads(plan: DeviceSpGEMMPlan,
                         a: Optional[CSC] = None,
                         b: Optional[CSC] = None
                         ) -> Tuple[Optional[np.ndarray],
                                    Optional[np.ndarray]]:
    """The host part of a values-only repack: what goes to the device for
    the changed side(s) of *structure-identical* operands (``None`` for
    an unchanged side, which costs nothing).

    Where the plan has slot maps, each side's new values per part
    (:func:`ring_values`, about nnz·4 bytes), which
    :class:`RingValueScatter` writes into fresh stacks on the device;
    where it has none (past :data:`POS_LIMIT`), the whole host-refilled
    stacks (:func:`refill_ring_stacks`), to be put as they are. Either
    way the schedules and the compiled executable are reused, and the
    answer is bitwise that of a cold re-plan.
    """
    if plan.a_pos is None or plan.b_pos is None:
        return refill_ring_stacks(plan, a, b)
    return tuple(None if m is None else ring_values(plan, i, m)
                 for i, m in enumerate((a, b)))


# ---------------------------------------------------------------------------
# device execution
# ---------------------------------------------------------------------------

def _make_step_fn(plan: DeviceSpGEMMPlan, axis: str, engine: str,
                  interpret: Optional[bool],
                  trace_probe: Optional[callable] = None):
    """The per-device body run under shard_map."""
    bs = plan.bs
    Pn = plan.nparts
    step_sizes = plan.step_sizes
    nc_max = plan.nc_max
    nprod_max = int(plan.a_slot.shape[1])
    semiring = plan.semiring
    chunk = plan.chunk
    seg_steps = plan.seg_steps
    seg_off = plan.seg_prod_off
    seg_len = plan.seg_prod_len
    # static offset of each ring step's slot run inside send_slots
    step_offs = [0]
    for mx in step_sizes:
        step_offs.append(step_offs[-1] + mx)

    def body(a_tiles, b_tiles, send_slots, a_slot, b_slot, c_slot, flags):
        # the body only executes while being traced, so a host-side callback
        # here counts (re)traces exactly — the session's compile-count probe
        if trace_probe is not None:
            trace_probe()
        # shapes inside shard_map (leading P axis stripped):
        # a_tiles (na_max, bs, bs); send_slots (S_total,); a_slot (nprod,)
        a_tiles = a_tiles[0]
        b_tiles = b_tiles[0]
        send_slots = send_slots[0]
        a_slot, b_slot, c_slot = a_slot[0], b_slot[0], c_slot[0]
        flags = flags[0]

        def fetch_step(s_idx):
            # one ring step: pack the requested payload tiles, one
            # collective permute at shift -(s_idx+1). Pad payloads carry
            # the additive identity, like every other pad.
            s = s_idx + 1
            slots = jax.lax.dynamic_slice_in_dim(
                send_slots, step_offs[s_idx], step_sizes[s_idx])
            payload = jnp.where(
                (slots >= 0)[:, None, None],
                a_tiles[jnp.clip(slots, 0, None)], semiring.zero)
            return jax.lax.ppermute(
                payload, axis,
                perm=[(j, (j - s) % Pn) for j in range(Pn)])

        if chunk is None:
            # ---- legacy single-pass ring: fetch everything, then one
            # schedule launch over the combined stack ------------------------
            recv = [a_tiles]
            for s_idx, mx in enumerate(step_sizes):
                if mx == 0:
                    continue
                recv.append(fetch_step(s_idx))
            stack = (jnp.concatenate(recv, axis=0)
                     if len(recv) > 1 else recv[0])

            # both engines write pad products into the trailing garbage slot
            # (nc_max), dropped here; neither needs a validity mask.
            out = run_schedule(stack, b_tiles, a_slot, b_slot, c_slot, flags,
                               engine=engine, nprod_max=nprod_max,
                               nc_max=nc_max, bs=bs, interpret=interpret,
                               semiring=semiring)
            return out[:nc_max][None]  # drop garbage slot, restore P axis

        # ---- chunked double-buffered pipeline ------------------------------
        # Chunk g+1's ppermutes depend only on the resident own stack and
        # the send-slot table — never on a partial result — so issuing them
        # before chunk g's schedule segment lets the compiler overlap the
        # collective with the compute it hides behind (the XLA analogue of
        # the paper's MPI_Get-while-computing), while only two chunk
        # payloads are ever live (cur + nxt) instead of the whole stack.
        def fetch_segment(g):
            parts = [fetch_step(s_idx) for s_idx in seg_steps[g]
                     if step_sizes[s_idx] > 0]
            if not parts:
                return None
            return jnp.concatenate(parts, axis=0) if len(parts) > 1 \
                else parts[0]

        def compute_segment(g, payload):
            # segment-offset launch over the flat schedule arrays; the
            # partial's unvisited output slots are masked to the additive
            # identity (the Pallas kernel leaves them unspecified, the jnp
            # reference leaves the reduce op's own identity) before the
            # cross-segment combine.
            off, ln = seg_off[g], seg_len[g]
            partial = run_schedule(payload, b_tiles, a_slot, b_slot, c_slot,
                                   flags, engine=engine, nprod_max=ln,
                                   nc_max=nc_max, bs=bs, interpret=interpret,
                                   semiring=semiring, seg_start=off)
            c_seg = c_slot[off:off + ln]
            visited = jax.ops.segment_sum(
                jnp.ones_like(c_seg), c_seg,
                num_segments=nc_max + 1) > 0
            return jnp.where(visited[:, None, None], partial,
                             jnp.asarray(semiring.zero, partial.dtype))

        G = len(seg_steps)
        acc = jnp.full((nc_max + 1, bs, bs), semiring.zero,
                       dtype=jnp.float32)
        cur = a_tiles  # segment 0's payload is the resident own stack
        for g in range(G):
            nxt = fetch_segment(g + 1) if g + 1 < G else None
            if seg_len[g] > 0 and cur is not None:
                acc = semiring.jnp_add(acc, compute_segment(g, cur))
            cur = nxt
        return acc[:nc_max][None]

    return body


def compile_ring(plan: DeviceSpGEMMPlan,
                 mesh: Optional[Mesh] = None,
                 axis: str = "p",
                 engine: str = "auto",
                 interpret: Optional[bool] = None,
                 semiring: Optional[Semiring] = None,
                 trace_probe: Optional[callable] = None):
    """Device-put the plan and jit the ring; returns ``(fn, args)``.

    ``fn(*args)`` yields the raw ``(P, nc_max, bs, bs)`` output stacks.
    Split out from :func:`run_device_spgemm` so benchmarks can warm the
    jit cache once and time repeated executions of the same compiled
    callable (a fresh closure per call would re-trace every time).
    ``trace_probe`` (if given) is invoked from the traced body at
    trace time only — the session uses it to assert zero retraces on
    cache hits.
    """
    check_plan_semiring(plan.semiring, semiring)
    if mesh is None:
        mesh = cpu_device_mesh(plan.nparts, axis)

    fn = ring_program(plan, mesh, axis, engine, interpret, trace_probe)
    sharded = NamedSharding(mesh, P(axis))
    return fn, [jax.device_put(x, sharded) for x in ring_args(plan)]


def ring_args(plan: DeviceSpGEMMPlan) -> Tuple[np.ndarray, ...]:
    """The host arrays :func:`ring_program` takes, in order; each has the
    ring's part axis leading."""
    return (plan.a_tiles, plan.b_tiles, plan.send_slots,
            plan.a_slot, plan.b_slot, plan.c_slot, plan.flags)


def ring_program(plan: DeviceSpGEMMPlan, mesh: Mesh,
                 axis: str = "p", engine: str = "auto",
                 interpret: Optional[bool] = None,
                 trace_probe: Optional[callable] = None):
    """The jitted shard_map ring over ``mesh``, placing nothing.

    Takes :func:`ring_args` sharded ``P(axis)`` over ``mesh``. Unlike
    :func:`compile_ring`, which places the plan first, it lets a caller
    lower and compile the ring for devices it cannot place arrays on, such
    as a described TPU topology, from shapes alone."""
    body = _make_step_fn(plan, axis, resolve_engine(engine), interpret,
                         trace_probe)
    # check_rep=False: the kernel's out_shape carries no vma (see
    # repro.compat.shard_map); nothing here is replicated.
    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(axis),) * 7,
        out_specs=P(axis), check_rep=False))


def _scatter_program(stack_shape: Tuple[int, ...], width: int, dtype,
                     zero: float, sharding: NamedSharding):
    """A compiled ``(pos, vals) -> stack`` over the ring's mesh: each
    device builds its payload stack fresh from the semiring's additive
    identity and writes its values through its slot map's ascending
    positions (pads dropped)."""
    axis = sharding.spec[0]
    inner = tuple(int(x) for x in stack_shape[1:])
    size = int(np.prod(inner))

    def body(pos, vals):
        flat = jnp.full((size,), zero, dtype=dtype).at[pos[0]].set(
            vals[0], mode="drop", indices_are_sorted=True,
            unique_indices=True)
        return flat.reshape(inner)[None]

    fn = jax.jit(shard_map(body, mesh=sharding.mesh,
                           in_specs=(P(axis), P(axis)),
                           out_specs=P(axis), check_rep=False))
    rows = int(stack_shape[0])
    return fn.lower(
        jax.ShapeDtypeStruct((rows, width), np.int32, sharding=sharding),
        jax.ShapeDtypeStruct((rows, width), dtype, sharding=sharding),
    ).compile()


class RingValueScatter:
    """A ring plan's values-only repack on the device.

    Holds the plan's slot maps (:func:`slot_map`) on the device and one
    compiled scatter per payload stack shape; called with the per-part
    values of :func:`repack_ring_payloads` (in the maps' order), it puts
    them on the device and scatters them into fresh stacks, bitwise equal
    to :func:`refill_ring_stacks`'s. Operands must be structure-identical
    to the plan's and free of duplicate entries, as ingress validation
    guarantees."""

    def __init__(self, plan: DeviceSpGEMMPlan, sharding: NamedSharding):
        self.sharding = sharding
        maps = (plan.a_pos, plan.b_pos)
        self.maps = tuple(jax.device_put(m, sharding) for m in maps)
        # A·A on one part has two stacks of one shape: one program
        shapes = [(plan.a_tiles.shape, plan.a_pos.shape[1]),
                  (plan.b_tiles.shape, plan.b_pos.shape[1])]
        programs = {k: _scatter_program(*k, plan.a_tiles.dtype,
                                        plan.semiring.zero, sharding)
                    for k in set(shapes)}
        self.programs = tuple(programs[k] for k in shapes)
        self.nbytes = sum(m.nbytes for m in maps)

    def __call__(self, vals: Sequence[Optional[np.ndarray]]
                 ) -> Tuple[Optional[jax.Array], ...]:
        """Fresh device stacks for the sides whose ``vals`` are given
        (None elsewhere), ready on return: the values and the scatters'
        scratch are freed before the product's output is allocated, so
        they add nothing to the execute's peak memory, and a scatter that
        fails on the device fails here."""
        return jax.block_until_ready(tuple(
            None if v is None else
            self.programs[i](self.maps[i], jax.device_put(v, self.sharding))
            for i, v in enumerate(vals)))


def compile_ring_scatter(plan: DeviceSpGEMMPlan, sharding: NamedSharding
                         ) -> Optional[RingValueScatter]:
    """The plan's :class:`RingValueScatter` over ``sharding`` (the
    sharding of :func:`compile_ring`'s args), or None where a slot map is
    out of int32's reach and :func:`repack_ring_payloads` hands over
    host-refilled stacks instead."""
    if plan.a_pos is None or plan.b_pos is None:
        return None
    return RingValueScatter(plan, sharding)


def ring_decode_map(plan: DeviceSpGEMMPlan, a: CSC, b: CSC) -> DecodeMap:
    """The plan's :class:`~repro.core.device_common.DecodeMap`, from its
    operands ``a`` and ``b`` (any values; only their structure is read).

    C's structural pattern is the symbolic product of A's and B's stored
    patterns (scipy, all-one data, rows sorted within each column). Entry
    ``(r, c)`` lies in part ``d`` of ``part_n`` at local column ``l = c -
    splits[d]``, in the output tile slot ``t`` of tile ``(r // bs, l //
    bs)`` among the part's ``c_rows``/``c_cols``, at flat position
    ``((d·nc_max + t)·bs + r mod bs)·bs + l mod bs`` of the fetched
    stack: the inverse of the scan decode's coordinates. Raises where a
    structural tile is none of the plan's output tiles."""
    import scipy.sparse as sp

    D, nc_max, bs = plan.nparts, plan.nc_max, plan.bs
    m, n = plan.out_shape
    splits = plan.part_n.splits.astype(np.int64)
    with span("spgemm.decode.structure") as s:
        pa, pb = (sp.csc_array((np.ones(x.nnz, np.float32), x.indices,
                                x.indptr), shape=x.shape).tocsr()
                  for x in (a, b))
        # the product in CSR, then to CSC: the conversion sorts each
        # column's rows
        pc = (pa @ pb).tocsc()
        indptr = pc.indptr.astype(np.int64, copy=False)
        indices = pc.indices.astype(np.int64, copy=False)
        del pa, pb, pc
        counts = np.diff(indptr)
        # per column: its part, and its local tile column and offset in it
        col_part = np.searchsorted(splits[1:], np.arange(n), side="right")
        ltile, lin = np.divmod(np.arange(n) - splits[col_part], bs)
        rtile, rin = np.divmod(indices, bs)
        # the plan's output tiles, keyed (part, tile row, tile col)
        ntr = max(-(-m // bs), 1)
        nct = max(-(-int(np.diff(splits).max(initial=0)) // bs), 1)
        td, tslot = np.nonzero(np.arange(nc_max)[None, :]
                               < plan.c_counts[:, None])
        tile_key = ((td * ntr + plan.c_rows[td, tslot]) * nct
                    + plan.c_cols[td, tslot])
        order = np.argsort(tile_key)
        tile_key, tslot = tile_key[order], tslot[order]
        key = np.repeat(col_part * ntr * nct + ltile, counts) + rtile * nct
        at = np.minimum(np.searchsorted(tile_key, key),
                        max(len(tile_key) - 1, 0))
        if len(key) and (not len(tile_key)
                         or not np.array_equal(tile_key[at], key)):
            raise ValueError("a structural tile of A·B is none of the "
                             "plan's output tiles")
        del key, rtile
        # intp, not int32: numpy's take converts other index types to
        # intp on every call, a copy as large as the map
        pos = (np.repeat(col_part * nc_max * bs * bs + lin, counts)
               + tslot[at] * (bs * bs) + rin * bs).astype(np.intp,
                                                         copy=False)
        s.set_metadata(nnz=len(indices))
    indptr.flags.writeable = False
    indices.flags.writeable = False
    return DecodeMap(indptr=indptr, indices=indices, pos=pos,
                     stack_shape=(D, nc_max, bs, bs), out_shape=(m, n),
                     tiles=tuple(np.array(x) for x in (
                         plan.c_rows, plan.c_cols, plan.c_counts,
                         splits[:-1], splits[1:])))


def decode_ring_output(plan: DeviceSpGEMMPlan, out: np.ndarray) -> CSC:
    """Decode the raw ``(P, nc_max, bs, bs)`` ring output to a global CSC.

    The shared semiring-aware decode (``device_common.decode_tiles``): each
    device's output-tile columns are local to its ``part_n`` slice, so the
    part's element offset is added and columns are clipped at the part's
    upper boundary before the single global COO assembly. Where the plan
    holds a structural map (:func:`ring_decode_map`), the decode gathers
    through it instead.
    """
    splits = plan.part_n.splits.astype(np.int64)
    return decode_tiles(out, plan.c_rows, plan.c_cols, plan.c_counts,
                        plan.semiring, plan.out_shape,
                        col_off=splits[:-1], col_lim=splits[1:],
                        decode_map=plan.decode_map)


def run_device_spgemm(plan: DeviceSpGEMMPlan,
                      mesh: Optional[Mesh] = None,
                      axis: str = "p",
                      engine: str = "auto",
                      interpret: Optional[bool] = None,
                      semiring: Optional[Semiring] = None) -> CSC:
    """Execute the plan across the devices of ``mesh`` and decode C."""
    check_plan_semiring(plan.semiring, semiring)
    fn, args = compile_ring(plan, mesh, axis, engine, interpret)
    return decode_ring_output(plan, np.asarray(fn(*args)))
