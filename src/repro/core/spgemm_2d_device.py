"""Device execution of sparse 2D SUMMA [Buluc & Gilbert '11] — shard_map grid.

This is the TPU translation of the sparsity-*oblivious* baseline the paper
compares its 1D algorithm against (CombBLAS's default). The MPI original
runs ``grid`` stages on a ``grid x grid`` process mesh: stage ``s``
broadcasts A's block-column ``s`` along process rows (``MPI_Bcast`` in the
row communicator) and B's block-row ``s`` along process columns; every
process multiplies and accumulates into its local C block.

XLA has no rooted broadcast collective, so the stage loop is realized the
static-shape way — the same translation ``spgemm_1d_device.py`` applies to
``MPI_Get``:

    the union of all ``grid`` stage broadcasts a device will receive is
    one ``all_gather`` over the mesh axis it shares with the senders:
    ``all_gather(A_local, "gc")`` delivers every A block of my process row
    (indexed by stage), ``all_gather(B_local, "gr")`` every B block of my
    process column. Stage s's broadcast is then slots ``[s*na_max, ...)``
    of the gathered stack, and the per-stage multiply-accumulate collapses
    into ONE product schedule over the combined stacks, executed by the
    revisit-free Pallas BSR kernel (``kernels/bsr_spgemm`` via
    ``kernels/launch``) exactly like the ring's compute phase.

Being oblivious is the point: the gather moves *whole blocks* regardless of
whether the receiver's schedule touches them — that is the communication
the sparsity-aware 1D algorithm avoids, and the padded-vs-planned stats
make the price visible on the same stats surface
(``device_common.REQUIRED_STATS``) as the 1D engine.

The same machinery generalizes to Split-3D-SpGEMM by adding a third mesh
axis: ``build_summa_plan(..., layers=L)`` splits the contraction dimension
across ``L`` layers (each runs its own 2D SUMMA on its k-slice) and the
partial C stacks are merged with one semiring all-reduce over the layer
axis (``Semiring.jnp_axis_reduce``: psum / pmax / pmin — the additive
monoid of every registered semiring has a native XLA collective). Output
slots are the *union* of the layers' output tiles so the reduce is
elementwise; slots a layer's schedule never visits are reset to the
additive identity before reducing (the revisit-free kernel leaves them
unspecified). ``spgemm_3d_device.py`` documents the 3D reading; this
module owns the machinery for both.

Everything is semiring-generic per the ROADMAP contract: payload pads,
unvisited-slot resets, the cross-layer reduce and the output decode all go
through the plan's semiring — no literal ``0.0`` anywhere.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compat import shard_map
from .blocksparse import BlockSparse, build_schedule, from_csc
from .device_common import (check_plan_semiring, decode_tiles,
                            device_grid_mesh, pack_schedules, resolve_engine,
                            run_schedule, snap_to_tiles)
from .plan import BYTES_PER_NNZ, Partition1D
from .semiring import PLUS_TIMES, Semiring
from .sparse import CSC, from_coo

__all__ = ["SummaDevicePlan", "build_summa_plan", "compile_summa",
           "run_device_summa", "decode_summa_output",
           "repack_summa_payloads"]


@dataclasses.dataclass
class SummaDevicePlan:
    """Static-shape plan for one device SUMMA call (2D, or 3D when
    ``layers > 1``). Leading array dims are the mesh: (grid, grid, layers)."""

    grid: int
    layers: int
    bs: int
    # per-device payload stacks (numpy, to be device_put sharded):
    a_tiles: np.ndarray        # (grid, grid, layers, na_max, bs, bs)
    b_tiles: np.ndarray        # (grid, grid, layers, nb_max, bs, bs)
    # per-device product schedule over the gathered stacks (pad products:
    # a_slot/b_slot 0, c_slot nc_max — the garbage slot):
    a_slot: np.ndarray         # (grid, grid, layers, nprod_max) i32
    b_slot: np.ndarray         # (grid, grid, layers, nprod_max) i32
    c_slot: np.ndarray         # (grid, grid, layers, nprod_max) i32
    flags: np.ndarray          # (grid, grid, layers, nprod_max) i32
    # union-slot visit mask per layer (slots this layer's schedule writes;
    # the rest are reset to the additive identity before the layer reduce):
    visit: np.ndarray          # (grid, grid, layers, nc_max + 1) bool
    nc_max: int
    # decode info, per (r, c) — identical across layers by construction:
    c_rows: np.ndarray         # (grid*grid, nc_max) global tile rows
    c_cols: np.ndarray         # (grid*grid, nc_max) global tile cols
    c_counts: np.ndarray       # (grid*grid,) real (union) output-tile count
    # the element partitions the blocks were cut on (tile-aligned):
    part_m: Partition1D        # rows of A / C, grid parts
    part_n: Partition1D        # cols of B / C, grid parts
    part_k: Partition1D        # contraction dim, grid*layers parts:
    #                            piece l*grid + s = layer l, stage s
    out_shape: Tuple[int, int]
    semiring: Semiring
    exact_bytes: int           # real tiles moved (gathers + layer merge)
    padded_bytes: int          # what the static-shape collectives move
    stats: dict


def _split_rows(sub: CSC, row_part: Partition1D) -> list:
    """Cut a column slice into its row blocks with ONE COO pass: each
    returned CSC is block ``r`` = rows ``row_part[r]`` of ``sub`` (local
    row ids). Replaces per-(row-block) re-slicing of the same columns."""
    rows, cols, vals = sub.to_coo()
    ri = np.searchsorted(row_part.splits, rows, side="right") - 1
    out = []
    for r in range(row_part.nparts):
        rlo, rhi = row_part.part_slice(r)
        keep = ri == r
        out.append(from_coo(rows[keep] - rlo, cols[keep], vals[keep],
                            (max(rhi - rlo, 0), sub.ncols)))
    return out


def _blockize_mesh_a(a: CSC, grid: int, layers: int, bs: int, dtype,
                     semiring: Semiring, part_m: Partition1D,
                     part_k: Partition1D):
    """a_blk[r][s][l]: A rows part_m[r] × k-piece (l*grid + s), owner
    (r, s, l); plus per-block stored-entry counts (explicit identity-valued
    entries included — an oblivious SUMMA moves stored entries regardless
    of value) for the element-level comm model."""
    fill = semiring.zero
    a_blk = [[[None] * layers for _ in range(grid)] for _ in range(grid)]
    a_nnzb = np.zeros((grid, grid, layers), dtype=np.int64)
    for l in range(layers):
        for s in range(grid):
            # slice each k-piece of A once, then bin its rows into the
            # grid row blocks in one COO pass (not grid re-slices)
            klo, khi = part_k.part_slice(l * grid + s)
            for r, blk in enumerate(_split_rows(a.col_slice(klo, khi),
                                                part_m)):
                a_blk[r][s][l] = from_csc(blk, bs=bs, dtype=dtype, fill=fill)
                a_nnzb[r, s, l] = blk.nnz
    return a_blk, a_nnzb


def _blockize_mesh_b(b: CSC, grid: int, layers: int, bs: int, dtype,
                     semiring: Semiring, part_n: Partition1D,
                     part_k: Partition1D):
    """b_blk[s][c][l]: B k-piece (l*grid + s) × cols part_n[c], owner
    (s, c, l); counts as in :func:`_blockize_mesh_a`."""
    fill = semiring.zero
    b_blk = [[[None] * layers for _ in range(grid)] for _ in range(grid)]
    b_nnzb = np.zeros((grid, grid, layers), dtype=np.int64)
    for c in range(grid):
        # each column part of B once, rows binned into the grid*layers
        # k-pieces
        nlo, nhi = part_n.part_slice(c)
        for p, blk in enumerate(_split_rows(b.col_slice(nlo, nhi), part_k)):
            b_blk[p % grid][c][p // grid] = from_csc(blk, bs=bs, dtype=dtype,
                                                     fill=fill)
            b_nnzb[p % grid, c, p // grid] = blk.nnz
    return b_blk, b_nnzb


def _pack_side(blk, grid: int, layers: int, max_n: int, bs: int, dtype,
               semiring: Semiring) -> np.ndarray:
    """Fill one static (grid, grid, layers, max_n, bs, bs) payload stack
    from a per-owner blockization (pads hold the additive identity)."""
    tiles = semiring.fill((grid, grid, layers, max_n, bs, bs), dtype=dtype)
    for r in range(grid):
        for c in range(grid):
            for l in range(layers):
                xb = blk[r][c][l]
                if xb.ntiles:
                    tiles[r, c, l, :xb.ntiles] = xb.tiles
    return tiles


def build_summa_plan(a: CSC, b: CSC, grid: int,
                     layers: int = 1,
                     bs: int = 128,
                     dtype=np.float32,
                     semiring: Semiring = PLUS_TIMES) -> SummaDevicePlan:
    """Blockize A and B onto the (grid, grid, layers) mesh and build every
    device's product schedule over the post-gather stacks.

    All three element partitions are snapped to tile boundaries so block
    tile grids embed into the global tile space (empty blocks — small
    matrices, surplus layers — simply contribute zero tiles). ``semiring``
    fixes the payload fill exactly as in the 1D planner.
    """
    assert a.ncols == b.nrows
    t_plan0 = time.perf_counter()
    m, k, n = a.nrows, a.ncols, b.ncols
    part_m = snap_to_tiles(Partition1D.balanced(m, grid), bs)
    part_n = snap_to_tiles(Partition1D.balanced(n, grid), bs)
    part_k = snap_to_tiles(Partition1D.balanced(k, grid * layers), bs)
    mg = math.ceil(max(m, 1) / bs)
    kg = math.ceil(max(k, 1) / bs)
    ng = math.ceil(max(n, 1) / bs)

    row_tile_off = [part_m.part_slice(r)[0] // bs for r in range(grid)]
    k_tile_off = [part_k.part_slice(p)[0] // bs for p in range(grid * layers)]
    n_tile_off = [part_n.part_slice(c)[0] // bs for c in range(grid)]

    # ---- blockize every block of the 3D distribution -----------------------
    a_blk, a_nnzb = _blockize_mesh_a(a, grid, layers, bs, dtype, semiring,
                                     part_m, part_k)
    b_blk, b_nnzb = _blockize_mesh_b(b, grid, layers, bs, dtype, semiring,
                                     part_n, part_k)

    na_max = max((a_blk[r][s][l].ntiles for r in range(grid)
                  for s in range(grid) for l in range(layers)), default=0)
    nb_max = max((b_blk[s][c][l].ntiles for s in range(grid)
                  for c in range(grid) for l in range(layers)), default=0)
    max_na, max_nb = max(na_max, 1), max(nb_max, 1)

    a_tiles = _pack_side(a_blk, grid, layers, max_na, bs, dtype, semiring)
    b_tiles = _pack_side(b_blk, grid, layers, max_nb, bs, dtype, semiring)

    # ---- per-device schedules over the gathered stacks ---------------------
    # Gathered layout on device (r, c, l): stage s's A block occupies slots
    # [s*max_na, s*max_na + ntiles) of the A stack (all_gather over "gc"
    # orders by stage); B likewise over "gr". Virtual views carry *global*
    # tile coordinates, so one build_schedule join pairs tiles of equal
    # global k and merges all stages into one revisit-free schedule.
    scheds = []
    union_rows, union_cols, union_counts = [], [], []
    visit_sets = []            # per flat (r, c, l): visited union slots
    nprod_total = 0
    for r in range(grid):
        for c in range(grid):
            per_layer = []
            for l in range(layers):
                rows_l, cols_l, slots_l = [], [], []
                for s in range(grid):
                    blk = a_blk[r][s][l]
                    if blk.ntiles:
                        rows_l.append(blk.tile_rows + row_tile_off[r])
                        cols_l.append(blk.tile_cols
                                      + k_tile_off[l * grid + s])
                        slots_l.append(s * max_na
                                       + np.arange(blk.ntiles, dtype=np.int64))
                va_rows = (np.concatenate(rows_l).astype(np.int32)
                           if rows_l else np.zeros(0, np.int32))
                va_cols = (np.concatenate(cols_l).astype(np.int32)
                           if cols_l else np.zeros(0, np.int32))
                va_slots = (np.concatenate(slots_l)
                            if slots_l else np.zeros(0, np.int64))

                rows_l, cols_l, slots_l = [], [], []
                for s in range(grid):
                    blk = b_blk[s][c][l]
                    if blk.ntiles:
                        rows_l.append(blk.tile_rows
                                      + k_tile_off[l * grid + s])
                        cols_l.append(blk.tile_cols + n_tile_off[c])
                        slots_l.append(s * max_nb
                                       + np.arange(blk.ntiles, dtype=np.int64))
                vb_rows = (np.concatenate(rows_l).astype(np.int32)
                           if rows_l else np.zeros(0, np.int32))
                vb_cols = (np.concatenate(cols_l).astype(np.int32)
                           if cols_l else np.zeros(0, np.int32))
                vb_slots = (np.concatenate(slots_l)
                            if slots_l else np.zeros(0, np.int64))

                virt_a = BlockSparse(
                    tiles=np.zeros(  # replint: off=RS003 1x1 placeholder payloads; only tile coords feed build_schedule, values never read
                        (len(va_rows), 1, 1), dtype=dtype),
                    tile_rows=va_rows, tile_cols=va_cols,
                    shape=(mg * bs, kg * bs), orig_shape=(m, k), bs=bs)
                virt_b = BlockSparse(
                    tiles=np.zeros(  # replint: off=RS003 1x1 placeholder payloads; only tile coords feed build_schedule, values never read
                        (len(vb_rows), 1, 1), dtype=dtype),
                    tile_rows=vb_rows, tile_cols=vb_cols,
                    shape=(kg * bs, ng * bs), orig_shape=(k, n), bs=bs)
                sched = build_schedule(virt_a, virt_b)
                okeys = (sched.c_cols.astype(np.int64) * mg
                         + sched.c_rows)          # sorted (build_schedule)
                per_layer.append(
                    (va_slots[sched.a_slot].astype(np.int32),
                     vb_slots[sched.b_slot].astype(np.int32),
                     sched.c_slot, okeys))
                nprod_total += sched.nprod

            # union of output tiles across layers: the cross-layer reduce is
            # elementwise, so every layer's schedule retargets union slots
            union = (np.unique(np.concatenate([p[3] for p in per_layer]))
                     if layers > 1 else per_layer[0][3])
            u_rows = (union % mg).astype(np.int32)
            u_cols = (union // mg).astype(np.int32)
            union_rows.append(u_rows)
            union_cols.append(u_cols)
            union_counts.append(len(union))
            for a_sl, b_sl, c_sl, okeys in per_layer:
                remap = np.searchsorted(union, okeys)
                c_union = (remap[c_sl].astype(np.int32)
                           if len(c_sl) else c_sl.astype(np.int32))
                scheds.append(dict(a_slot=a_sl, b_slot=b_sl, c_slot=c_union,
                                   c_rows=u_rows, c_cols=u_cols))
                visit_sets.append(np.unique(c_union))

    packed = pack_schedules(scheds)
    nprod_max, nc_max = packed["nprod_max"], packed["nc_max"]
    D = grid * grid * layers

    visit = np.zeros((D, nc_max + 1), dtype=bool)
    for d, vs in enumerate(visit_sets):
        visit[d, vs] = True
        visit[d, nc_max] = True   # garbage slot: every pad product hits it

    # per-(r, c) decode arrays: layer 0's row of the packed stack (identical
    # across layers — all carry the union coords)
    lead = np.arange(0, D, layers)
    c_rows = packed["c_rows"][lead]
    c_cols = packed["c_cols"][lead]
    c_counts = packed["c_counts"][lead]

    # ---- communication accounting ------------------------------------------
    # gathers: device (r,c,l) receives every A block of its process row but
    # its own, and every B block of its process column but its own
    tile_bytes = bs * bs * np.dtype(dtype).itemsize
    a_ntiles = np.array([[[a_blk[r][s][l].ntiles for l in range(layers)]
                          for s in range(grid)] for r in range(grid)])
    b_ntiles = np.array([[[b_blk[s][c][l].ntiles for l in range(layers)]
                          for c in range(grid)] for s in range(grid)])
    gather_exact = 0
    for r in range(grid):
        for c in range(grid):
            for l in range(layers):
                gather_exact += (a_ntiles[r, :, l].sum() - a_ntiles[r, c, l]
                                 + b_ntiles[:, c, l].sum()
                                 - b_ntiles[r, c, l])
    gather_padded = D * (grid - 1) * (max_na + max_nb)
    # layer merge: every non-root layer's padded partial stack moves once
    merge_exact = (layers - 1) * int(sum(union_counts))
    merge_padded = (layers - 1) * grid * grid * nc_max
    exact_tiles = int(gather_exact) + merge_exact
    padded_tiles = gather_padded + merge_padded

    # element-level model of the gather volume (stored entries inside the
    # moved blocks, BYTES_PER_NNZ each). Counted during the row-binning
    # blockize above — a path independent of ``plan.summa2d_comm_volume``'s
    # COO binning, which it must agree with on the same partitions (pinned
    # by tests/test_device_engines.py). Stored entries equal to the
    # semiring identity count too: the oblivious algorithm ships them like
    # any other payload. The layer merge is excluded: its element volume
    # needs the partial products' nnz (see ``plan.summa3d_comm_volume``
    # for the host model).
    model_per_proc = np.zeros((grid, grid), dtype=np.int64)
    for r in range(grid):
        for c in range(grid):
            recv = 0
            for l in range(layers):
                recv += (a_nnzb[r, :, l].sum() - a_nnzb[r, c, l]
                         + b_nnzb[:, c, l].sum() - b_nnzb[r, c, l])
            model_per_proc[r, c] = recv * BYTES_PER_NNZ

    messages = D * 2 * (grid - 1) + grid * grid * (layers - 1)
    plan_seconds = time.perf_counter() - t_plan0

    def _reshape(x):
        return x.reshape((grid, grid, layers) + x.shape[1:])

    return SummaDevicePlan(
        grid=grid, layers=layers, bs=bs,
        a_tiles=a_tiles, b_tiles=b_tiles,
        a_slot=_reshape(packed["a_slot"]), b_slot=_reshape(packed["b_slot"]),
        c_slot=_reshape(packed["c_slot"]), flags=_reshape(packed["flags"]),
        visit=_reshape(visit), nc_max=nc_max,
        c_rows=c_rows, c_cols=c_cols, c_counts=c_counts,
        part_m=part_m, part_n=part_n, part_k=part_k,
        out_shape=(m, n), semiring=semiring,
        exact_bytes=exact_tiles * tile_bytes,
        padded_bytes=padded_tiles * tile_bytes,
        stats=dict(
            # shared device-engine stats surface (device_common.REQUIRED_STATS)
            comm_bytes_planned=exact_tiles * tile_bytes,
            comm_bytes_padded=padded_tiles * tile_bytes,
            messages=int(messages),
            dense_flops=2 * nprod_total * bs ** 3,
            plan_seconds=plan_seconds,
            # SUMMA gathers the whole process-row/column working set up
            # front and runs one schedule pass: no chunking, no overlap,
            # and the per-device payload peak is the full gathered stack
            peak_payload_tiles=int((grid - 1) * (max_na + max_nb)
                                   + max_na + max_nb),
            chunks=1,
            overlap_fraction=0.0,
            # SUMMA-specific detail
            na_max=na_max, nb_max=nb_max, nprod_max=int(nprod_max),
            nprod_total=int(nprod_total), nc_max=int(nc_max),
            exact_tiles=exact_tiles, padded_tiles=int(padded_tiles),
            merge_tiles=merge_exact,
            comm_bytes_model=int(model_per_proc.sum()),
            comm_bytes_model_per_device=model_per_proc.reshape(-1),
        ),
    )


def repack_summa_payloads(plan: SummaDevicePlan,
                          a: Optional[CSC] = None,
                          b: Optional[CSC] = None
                          ) -> Tuple[Optional[np.ndarray],
                                     Optional[np.ndarray]]:
    """Fresh payload stacks for *structure-identical* operands.

    The SUMMA analogue of ``spgemm_1d_device.repack_ring_payloads``:
    re-blockize the changed side(s) on the plan's tile-snapped partitions
    and refill the static stacks (``None`` operand → ``None`` stack, so an
    unchanged operand is never re-blockized), leaving schedules / visit
    masks / decode coordinates untouched so the compiled executable can be
    reused without retracing (``core.session``'s values-only cache-hit
    path).
    """
    dtype = plan.a_tiles.dtype
    a_tiles = b_tiles = None
    if a is not None:
        a_blk, _ = _blockize_mesh_a(a, plan.grid, plan.layers, plan.bs,
                                    dtype, plan.semiring, plan.part_m,
                                    plan.part_k)
        a_tiles = _pack_side(a_blk, plan.grid, plan.layers,
                             plan.a_tiles.shape[3], plan.bs, dtype,
                             plan.semiring)
    if b is not None:
        b_blk, _ = _blockize_mesh_b(b, plan.grid, plan.layers, plan.bs,
                                    dtype, plan.semiring, plan.part_n,
                                    plan.part_k)
        b_tiles = _pack_side(b_blk, plan.grid, plan.layers,
                             plan.b_tiles.shape[3], plan.bs, dtype,
                             plan.semiring)
    return a_tiles, b_tiles


def _make_body(plan: SummaDevicePlan, axes, engine: str,
               interpret: Optional[bool],
               trace_probe: Optional[callable] = None):
    """The per-device body run under shard_map on the 3-axis mesh."""
    bs, layers = plan.bs, plan.layers
    nc_max = plan.nc_max
    nprod_max = int(plan.a_slot.shape[-1])
    semiring = plan.semiring
    ax_r, ax_c, ax_l = axes

    def body(a_tiles, b_tiles, a_slot, b_slot, c_slot, flags, visit):
        # the body only executes while being traced, so a host-side callback
        # here counts (re)traces exactly — the session's compile-count probe
        if trace_probe is not None:
            trace_probe()
        # shapes inside shard_map (leading (1,1,1) mesh block stripped)
        a_tiles = a_tiles[0, 0, 0]       # (max_na, bs, bs)
        b_tiles = b_tiles[0, 0, 0]
        a_slot, b_slot = a_slot[0, 0, 0], b_slot[0, 0, 0]
        c_slot, flags = c_slot[0, 0, 0], flags[0, 0, 0]
        visit = visit[0, 0, 0]           # (nc_max + 1,)

        # ---- fetch phase: the union of all stage broadcasts ----------------
        # all_gather over the column axis = every A block in my process row,
        # ordered by stage; over the row axis = every B block in my column.
        a_gath = jax.lax.all_gather(a_tiles, ax_c)   # (grid, max_na, bs, bs)
        b_gath = jax.lax.all_gather(b_tiles, ax_r)
        stack_a = a_gath.reshape((-1,) + a_gath.shape[-2:])
        stack_b = b_gath.reshape((-1,) + b_gath.shape[-2:])

        # ---- compute phase: one scheduled kernel over all stages -----------
        out = run_schedule(stack_a, stack_b, a_slot, b_slot, c_slot, flags,
                           engine=engine, nprod_max=nprod_max, nc_max=nc_max,
                           bs=bs, interpret=interpret, semiring=semiring)

        if layers > 1:
            # union slots this layer never wrote hold unspecified payloads
            # (revisit-free kernel) — reset them to the additive identity,
            # then merge the layers' partials through the semiring's monoid
            out = jnp.where(visit[:, None, None], out,
                            jnp.asarray(semiring.zero, out.dtype))
            out = semiring.jnp_axis_reduce(out, ax_l)
        return out[:nc_max][None, None, None]  # drop garbage slot

    return body


def compile_summa(plan: SummaDevicePlan,
                  mesh: Optional[Mesh] = None,
                  axes: Tuple[str, str, str] = ("gr", "gc", "gl"),
                  engine: str = "auto",
                  interpret: Optional[bool] = None,
                  semiring: Optional[Semiring] = None,
                  trace_probe: Optional[callable] = None):
    """Device-put the plan and jit the SUMMA body; returns ``(fn, args)``.

    ``fn(*args)`` yields the raw ``(grid, grid, layers, nc_max, bs, bs)``
    output stacks (identical across the layer axis after the merge). Split
    from :func:`run_device_summa` so benchmarks can warm the jit cache once
    and time repeated executions of the same compiled callable.
    ``trace_probe`` fires from the traced body at trace time only (the
    session's compile-count probe).
    """
    engine = resolve_engine(engine)
    check_plan_semiring(plan.semiring, semiring)
    if mesh is None:
        mesh = device_grid_mesh((plan.grid, plan.grid, plan.layers), axes)

    sharded = NamedSharding(mesh, P(*axes))
    args = [jax.device_put(x, sharded) for x in (
        plan.a_tiles, plan.b_tiles, plan.a_slot, plan.b_slot,
        plan.c_slot, plan.flags, plan.visit)]

    body = _make_body(plan, axes, engine, interpret, trace_probe)
    # check_rep=False: the kernel's out_shape carries no vma (see
    # repro.compat.shard_map); the layer reduce makes the output
    # replicated over the layer axis, which out_specs deliberately do not
    # claim.
    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(*axes),) * 7,
        out_specs=P(*axes), check_rep=False))
    return fn, args


def decode_summa_output(plan: SummaDevicePlan, out: np.ndarray) -> CSC:
    """Decode the raw mesh output to a global CSC (layer 0 carries the
    merged result; output tile coordinates are already global, and blocks
    are disjoint across the (r, c) mesh by the tile-aligned partitions)."""
    g2 = plan.grid * plan.grid
    lead = out[:, :, 0].reshape((g2, plan.nc_max, plan.bs, plan.bs))
    return decode_tiles(lead, plan.c_rows, plan.c_cols, plan.c_counts,
                        plan.semiring, plan.out_shape)


def run_device_summa(plan: SummaDevicePlan,
                     mesh: Optional[Mesh] = None,
                     axes: Tuple[str, str, str] = ("gr", "gc", "gl"),
                     engine: str = "auto",
                     interpret: Optional[bool] = None,
                     semiring: Optional[Semiring] = None) -> CSC:
    """Execute the plan across the mesh devices and decode C."""
    check_plan_semiring(plan.semiring, semiring)
    fn, args = compile_summa(plan, mesh, axes, engine, interpret)
    return decode_summa_output(plan, np.asarray(fn(*args)))
