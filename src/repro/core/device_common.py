"""Shared plan/compile/decode machinery for the device SpGEMM engines.

Three distributed SpGEMM algorithms run on the same shard_map + Pallas BSR
substrate:

  * ``spgemm_1d_device.py``  — the paper's sparsity-aware 1D ring,
  * ``spgemm_2d_device.py``  — sparse 2D SUMMA (sparsity-oblivious baseline),
  * ``spgemm_3d_device.py``  — Split-3D-SpGEMM (layered SUMMA + k-reduction).

Everything they have in common lives here, so a new engine is only the
algorithm-specific parts (who owns what, which collectives move it):

  * tile-aligned partition snapping and per-part blockization
    (:func:`snap_to_tiles`, :func:`blockize_parts`);
  * engine selection (``"pallas"`` product path / ``"jnp"`` reference,
    :func:`resolve_engine`) and the plan-vs-call semiring handshake
    (:func:`check_plan_semiring`);
  * static-shape packing of per-device product schedules with the
    garbage-slot pad convention (:func:`pack_schedules`);
  * the compute-phase dispatch to the scheduled revisit-free Pallas kernel
    or its segment-reduce reference (:func:`run_schedule`);
  * mesh construction over the host's visible devices
    (:func:`device_grid_mesh`);
  * the batched semiring-aware output decode (:func:`decode_tiles`);
  * the **shared stats surface**: every device plan's ``stats`` dict carries
    at least :data:`REQUIRED_STATS` — exact planned vs padded communication
    bytes, message count, dense MXU flops and planner wall time — so the
    1D/2D/3D engines can be compared row-for-row in
    ``benchmarks/device_compare.py``.

Everything here is host-side numpy except :func:`run_schedule`, which is
traced inside the engines' shard_map bodies.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..runtime.spans import span
from .blocksparse import BlockSparse, flags_from_c_slot, from_csc
from .plan import Partition1D
from .semiring import Semiring
from .sparse import CSC, from_coo

__all__ = [
    "ENGINES", "REQUIRED_STATS", "CHUNK_STATS", "SESSION_STATS",
    "snap_to_tiles", "blockize_parts", "resolve_engine",
    "check_plan_semiring", "pack_schedules", "run_schedule",
    "device_grid_mesh", "decode_tiles",
]

ENGINES = ("pallas", "jnp")

# the chunked-pipeline slice of the stats surface (PR 9 tentpole):
#   peak_payload_tiles : per-device A-side working set in tiles — own
#                        payload stack plus the fetched chunks resident at
#                        once (double-buffered: current + next chunk); the
#                        unchunked ring holds the whole gathered stack
#   chunks             : schedule segments the compute phase streams
#                        through (1 = legacy single-pass ring / SUMMA)
#   overlap_fraction   : modeled fraction of fetched (padded) tiles whose
#                        fetch is issued while a previous chunk's compute
#                        is outstanding (0.0 for unchunked engines; the
#                        measured counterpart is benchmarks/fig08
#                        --engine device)
CHUNK_STATS = ("peak_payload_tiles", "chunks", "overlap_fraction")

# every device plan's ``stats`` dict must carry these keys with these
# meanings (tests/test_device_engines.py pins the surface; replint RS015
# requires this to stay a literal tuple — it is the authoritative list the
# flow rules check plan builders against, so CHUNK_STATS above is spelled
# out again rather than concatenated):
#   comm_bytes_planned : payload bytes of real tiles the algorithm moves
#   comm_bytes_padded  : bytes the static-shape collectives actually move
#   messages           : planned point-to-point transfers (0 on a 1-device
#                        mesh — nothing ever leaves the device)
#   dense_flops        : MXU flops of the scheduled tile products
#   plan_seconds       : host planner wall time
#   peak_payload_tiles / chunks / overlap_fraction : CHUNK_STATS above
REQUIRED_STATS = ("comm_bytes_planned", "comm_bytes_padded", "messages",
                  "dense_flops", "plan_seconds",
                  "peak_payload_tiles", "chunks", "overlap_fraction")

# the persistent-session stats surface (``core.session.SpGEMMSession.stats``
# carries exactly these keys; tests/test_session.py pins the surface):
#   calls             : multiplies served by the session
#   plan_cache_hits   : structure-identical repeats that skipped planning
#   plan_cache_misses : cold keys that planned + compiled
#   plan_seconds_saved: sum of cached plans' plan_seconds over the hits
#                       that reused them (host planning time not re-spent)
#   payload_repacks   : hits whose operand *values* changed — payload
#                       stacks refilled, plan/executable reused
#   traces            : shard_map-body (re)traces observed via the
#                       compile-count probe; constant across cache hits
#   evictions         : LRU entries dropped at capacity
#   retries           : per-stage attempts repeated after a retryable
#                       failure (backoff handled by runtime.with_retries)
#   fallbacks         : degradation-ladder descents — a rung failed and the
#                       call moved to the next (engine pallas→jnp, then
#                       algorithm 3d→2d→1d)
#   quarantined       : cached entries dropped because a stage failed on
#                       them (poisoned executables never survive)
#   validation_failures : operands rejected at session ingress
#   bytes_cached      : device bytes currently pinned by cached entries'
#                       payload/schedule stacks (the quantity the LRU byte
#                       budgets bound; falls on eviction and quarantine)
SESSION_STATS = ("calls", "plan_cache_hits", "plan_cache_misses",
                 "plan_seconds_saved", "payload_repacks", "traces",
                 "evictions", "retries", "fallbacks", "quarantined",
                 "validation_failures", "bytes_cached")


def snap_to_tiles(part: Partition1D, bs: int) -> Partition1D:
    """Round interior split points to multiples of ``bs`` (monotone).

    Interior points are capped at ``ncols`` *before* the monotone sweep —
    rounding up past the end (bs > part width at the tail) must yield empty
    trailing parts, not grow the partition beyond the matrix.
    """
    splits = part.splits.copy()
    splits[1:-1] = np.minimum((splits[1:-1] + bs // 2) // bs * bs,
                              splits[-1])
    return Partition1D(np.maximum.accumulate(splits))


def blockize_parts(mat: CSC, part: Partition1D, bs: int,
                   dtype, fill: float) -> List[BlockSparse]:
    """Blockize each column part of ``mat`` independently.

    ``fill`` is deliberately required: it must be the executing semiring's
    additive identity (``Semiring.zero``) — defaulting to a literal 0.0
    here would silently hand min-plus engines zero-cost edges at absent
    positions (ROADMAP semiring contract)."""
    return [from_csc(mat.col_slice(*part.part_slice(i)), bs=bs, dtype=dtype,
                     fill=fill)
            for i in range(part.nparts)]


def resolve_engine(engine: str) -> str:
    """``"auto"`` resolves to the Pallas scheduled kernel — the product
    path on every backend (interpret mode covers CPU, cf.
    ``launch.resolve_interpret``); ``"jnp"`` selects the segment-sum
    reference formulation."""
    if engine == "auto":
        return "pallas"
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES + ('auto',)}, "
                         f"got {engine!r}")
    return engine


def check_plan_semiring(plan_semiring: Semiring,
                        semiring: Optional[Semiring]) -> Semiring:
    """A device plan's payloads are identity-filled at build time, so the
    semiring is baked in; an explicit argument is accepted for call-site
    clarity but must match the plan."""
    if semiring is None:
        return plan_semiring
    if semiring.name != plan_semiring.name:
        raise ValueError(
            f"plan was built for semiring {plan_semiring.name!r} "
            f"(payload pads are its identity); cannot execute under "
            f"{semiring.name!r} — rebuild the plan with semiring=")
    return semiring


def pack_schedules(scheds: Sequence[dict]) -> dict:
    """Pad per-device product schedules to one static shape.

    ``scheds[d]`` is a dict with keys ``a_slot``/``b_slot``/``c_slot``
    (equal-length product arrays, ``c_slot`` nondecreasing) and
    ``c_rows``/``c_cols`` (output-tile coordinates; their length is the
    device's real output-slot count, which may exceed the slots ``c_slot``
    actually visits — 3D union schedules leave layer-unvisited slots).

    Returns the padded stacks the shard_map bodies consume: pad products
    point at payload slot 0 and the trailing garbage output slot ``nc_max``
    (computed unmasked, dropped after the call), flags packed per device.
    """
    D = len(scheds)
    nprod_max = max((len(s["a_slot"]) for s in scheds), default=0)
    nc_max = max((len(s["c_rows"]) for s in scheds), default=0)
    nprod_max = max(nprod_max, 1)
    nc_max = max(nc_max, 1)
    A = np.zeros((D, nprod_max), dtype=np.int32)
    B = np.zeros((D, nprod_max), dtype=np.int32)
    C = np.full((D, nprod_max), nc_max, dtype=np.int32)
    c_rows = np.zeros((D, nc_max), dtype=np.int32)
    c_cols = np.zeros((D, nc_max), dtype=np.int32)
    c_counts = np.zeros(D, dtype=np.int64)
    for d, s in enumerate(scheds):
        n = len(s["a_slot"])
        A[d, :n] = s["a_slot"]
        B[d, :n] = s["b_slot"]
        C[d, :n] = s["c_slot"]
        nc = len(s["c_rows"])
        c_rows[d, :nc] = s["c_rows"]
        c_cols[d, :nc] = s["c_cols"]
        c_counts[d] = nc
    return dict(a_slot=A, b_slot=B, c_slot=C, flags=flags_from_c_slot(C),
                c_rows=c_rows, c_cols=c_cols, c_counts=c_counts,
                nprod_max=int(nprod_max), nc_max=int(nc_max))


def run_schedule(stack_a, stack_b, a_slot, b_slot, c_slot, flags, *,
                 engine: str, nprod_max: int, nc_max: int, bs: int,
                 interpret, semiring: Semiring, seg_start: int = 0):
    """Compute phase shared by every engine body (traced under shard_map).

    Streams the padded per-device schedule over the payload stacks through
    the revisit-free Pallas BSR kernel (``engine="pallas"``, the product
    path) or the segment-reduce reference (``engine="jnp"``). Returns the
    ``(nc_max + 1, bs, bs)`` output stack *including* the trailing garbage
    slot every pad product targets — callers drop it.

    ``seg_start``/``nprod_max`` select one contiguous schedule segment
    (static offset + length): the chunked 1D ring calls this once per
    payload chunk over the same flat schedule arrays, and the per-segment
    partials are combined by the caller under the semiring's additive
    monoid. The default ``seg_start=0`` with the full length is the legacy
    single-pass launch.
    """
    from ..kernels.bsr_spgemm.kernel import bsr_spgemm_pallas
    from ..kernels.bsr_spgemm.ref import bsr_spgemm_ref

    if engine == "pallas":
        return bsr_spgemm_pallas(
            stack_a, stack_b, a_slot, b_slot, c_slot, flags,
            nprod=nprod_max, nc=nc_max + 1, bs=bs, interpret=interpret,
            semiring=semiring, seg_start=seg_start)
    return bsr_spgemm_ref(
        stack_a, stack_b, a_slot, b_slot, c_slot, nc=nc_max + 1,
        semiring=semiring, seg_start=seg_start, seg_len=nprod_max)


def device_grid_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """A mesh of the first ``prod(shape)`` visible devices, reshaped to
    ``shape`` with named ``axes`` (the n-d generalization of
    ``repro.compat.cpu_device_mesh``). Raises when the process has fewer
    devices (see ``repro.compat.too_few_devices``)."""
    import jax
    from jax.sharding import Mesh

    from ..compat import too_few_devices

    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise ValueError(too_few_devices(need, len(devs),
                                         f" for a {shape} mesh"))
    return Mesh(np.array(devs[:need]).reshape(shape), axes)


def decode_tiles(out: np.ndarray, c_rows: np.ndarray, c_cols: np.ndarray,
                 c_counts: np.ndarray, semiring: Semiring,
                 out_shape: Tuple[int, int],
                 col_off: Optional[np.ndarray] = None,
                 col_lim: Optional[np.ndarray] = None) -> CSC:
    """Decode per-device output tile stacks into one global CSC.

    One batched prune-mask scan over every device's stack. Tiles past each
    device's real count are reset to the additive identity first: the
    Pallas engine never writes them (revisit-free flush touches exactly the
    scheduled slots), so their payloads are unspecified. The prune is the
    semiring's — an entry is dropped iff it equals the identity (0.0 for
    plus-times/bool, +inf for min-plus), never by a literal nonzero test.

    out      : (D, nc_max, bs, bs) device outputs (garbage slot dropped)
    c_rows   : (D, nc_max) global tile-grid rows of each output payload
    c_cols   : (D, nc_max) tile-grid cols — global, or local to a column
               part when ``col_off`` carries the per-device element offset
    c_counts : (D,) real output-tile count per device
    col_off  : (D,) element-column offset added per device (1D ring parts)
    col_lim  : (D,) exclusive global column bound per device (defaults to
               the matrix width; the 1D ring passes its part boundaries)
    """
    D, nc_max, bs, _ = out.shape
    if col_off is None:
        col_off = np.zeros(D, dtype=np.int64)
    if col_lim is None:
        col_lim = np.full(D, out_shape[1], dtype=np.int64)
    with span("spgemm.decode.prune"):
        valid_tile = (np.arange(nc_max)[None, :]
                      < np.asarray(c_counts)[:, None])
        out = np.where(valid_tile[:, :, None, None], out,
                       out.dtype.type(semiring.zero))
        ii, tt, rr, cc = np.nonzero(semiring.prune_mask(out))
        vals = out[ii, tt, rr, cc]
        rows_g = rr + c_rows[ii, tt].astype(np.int64) * bs
        cols_g = cc + c_cols[ii, tt].astype(np.int64) * bs + col_off[ii]
        keep = (rows_g < out_shape[0]) & (cols_g < col_lim[ii])
        rows_g, cols_g, vals = rows_g[keep], cols_g[keep], vals[keep]
    with span("spgemm.decode.assemble", nnz=len(vals)):
        return from_coo(rows_g, cols_g, vals, out_shape)
