"""Persistent device-SpGEMM sessions — structure-keyed plan/executable cache.

The paper's use cases are all *iterated* multiplies: BC expands a frontier
level after level, AMG re-builds Galerkin products per setup, Markov
clustering squares the same operator until convergence, and randomized
sketching applies one sketch to a stream of matrices. On the device path
the expensive work per multiply is **host planning** (symbolic phase,
schedule join, static-shape packing) and **tracing/compiling** the
shard_map ring — both of which depend only on the operands' *sparsity
structure* and the call geometry, never on the numeric values.

:class:`SpGEMMSession` exploits that split. A multiply is two steps:
:meth:`~SpGEMMSession.prepare` validates the operands and fingerprints them
once into a :class:`Call`; :meth:`~SpGEMMSession.run` serves it from an LRU
cache keyed (by ``_key`` alone) on

    (algorithm, mesh geometry (nparts / grid×layers), bs, nblocks and
     chunk (1D only), semiring, engine, payload dtype,
     structure fingerprint of A, structure fingerprint of B)

with three outcomes:

  * **cold key** — plan (``build_device_plan`` / ``build_summa_plan``),
    compile (``compile_ring`` / ``compile_summa``), cache plan +
    executable + device-resident args;
  * **hit, same values** — run the cached executable as-is: zero host
    planning, zero retrace, zero payload transfer;
  * **hit, new values** — the values-only path: refill the payload
    stacks and swap them into the cached device args, run the same
    executable. The 1D ring sends only the new values
    (``repack_ring_payloads``) and scatters them on the device through
    slot maps kept with the plan (``RingValueScatter``, compiled with the
    entry); the SUMMA engines re-blockize on the host
    (``repack_summa_payloads``). Still zero planning and zero retrace.

On a 1D ring plan's second use (either kind of hit) the session also
builds the plan's structural decode map (``ring_decode_map``), while the
device runs the product; every later decode of the plan gathers C's
entries through it instead of scanning the output stack. A structure
served once pays nothing for it.

``matmul`` is ``run(prepare(...))``; the serving layer groups prepared
calls on ``Call.group``. The session is only the cache (``maxsize``,
``max_bytes``, LRU order): ``last_call`` names the key that served a call
and what its budgets evicted, and the serving layer keeps tenant
accounting on top of ``entries`` and ``evict``.

Any structure change, semiring change, engine change or geometry change is
simply a different key — invalidation is by construction, not by mutation
tracking. Retrace-freedom is *observable*: the engines' ``trace_probe``
fires from the traced body only, so ``stats["traces"]`` counts real
(re)compilations (the surface is ``device_common.SESSION_STATS``).

Policy (ROADMAP): applications never call ``build_device_plan`` /
``compile_ring`` directly — BC, AMG, MCL and sketching all multiply
through a session, so every iterated workload amortizes planning for free.

Hardened-runtime contract (see ``core/validate.py`` for the taxonomy):
operands are validated at ingress (a malformed request raises
:class:`ValidationError` before it can touch the cache); every pipeline
stage (plan / compile / execute / repack) runs under seeded-jitter
exponential-backoff retries; a stage that stays broken walks the
**degradation ladder** — engine fallback pallas→jnp, then algorithm
downgrade 3d→2d→1d — and every rung is bitwise oracle-equivalent, so a
degraded answer is still *the* answer. The ladder is for faults of the
device and its runtime only: a program that fails to trace, lower or
compile raises :class:`CompileError` at once — no retry, no lower rung —
because the same program would fail the same way again, and a lower rung
would serve a broken product path on the reference engine unnoticed.
Cached entries whose stage fails are quarantined (dropped + device
buffers released) and a per-key circuit breaker stops re-planning a key
that keeps failing. Whatever escapes the ladder is a typed
:class:`SpGEMMError`; bare ``RuntimeError`` never leaks.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import OrderedDict
from typing import Callable, List, Optional, Tuple

import numpy as np

from ..runtime.fault_tolerance import RetryPolicy, with_retries
from ..runtime.spans import span
from .device_common import SESSION_STATS, resolve_engine
from .semiring import PLUS_TIMES, Semiring
from .sparse import CSC
from .validate import (CompileError, DeviceExecError, SpGEMMError,
                       ValidationError, validate_matmul_operands,
                       wrap_stage_error)

__all__ = ["SpGEMMSession", "Call", "session_or_new", "as_payload_dtype",
           "structure_fingerprint", "values_fingerprint", "ALGORITHMS",
           "DOWNGRADE"]

ALGORITHMS = ("1d", "2d", "3d")

# the algorithm rungs of the degradation ladder, most- to least-demanding;
# every rung is bitwise-pinned to the same host oracle, so a downgraded
# call returns the identical CSC — it just moves more bytes to get there
DOWNGRADE = {"1d": ("1d",), "2d": ("2d", "1d"), "3d": ("3d", "2d", "1d")}


def structure_fingerprint(mat: CSC) -> bytes:
    """Digest of the sparsity *structure* only: shape + indptr + indices.

    Two matrices with equal fingerprints blockize to identical tile
    layouts, so they share plans, schedules and compiled executables;
    values are deliberately excluded (they only affect payload contents).
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(np.asarray(mat.shape, dtype=np.int64).tobytes())
    h.update(mat.indptr.tobytes())
    h.update(mat.indices.tobytes())
    return h.digest()


def values_fingerprint(mat: CSC) -> bytes:
    """Digest of the stored values (used to skip the payload repack when a
    structure-identical repeat also carries bit-identical values)."""
    h = hashlib.blake2b(digest_size=16)
    h.update(mat.data.tobytes())
    return h.digest()


def _stored_entries(*mats) -> int:
    """Stored entries of the operands, a span counter read before
    validation: O(1), and 0 for an operand whose data is no array."""
    return sum(m.data.size for m in mats
               if isinstance(getattr(m, "data", None), np.ndarray))


def _tile_slots(plan, a: Optional[CSC], b: Optional[CSC]) -> int:
    """Payload tile slots a values-only repack refills for the changed
    sides (a span counter: O(1))."""
    return sum(int(np.prod(stack.shape[:-2]))
               for m, stack in ((a, plan.a_tiles), (b, plan.b_tiles))
               if m is not None)


def as_payload_dtype(mat: CSC, dtype=np.float32) -> CSC:
    """Cast an operand's data to the session's payload dtype, explicitly.

    Sessions compute in ``dtype`` (default float32) regardless of the
    operand's host dtype; the cast used to happen silently inside
    blockization. Values-only repacks now *reject* dtype-mismatched
    operands (see :meth:`SpGEMMSession.matmul`), so iterated workloads
    whose host arithmetic runs in float64 (BC's σ/δ sweeps, MCL's
    inflation) cast at the call site — once, visibly — before handing
    operands to the session. A no-op (no copy) when the dtype already
    matches; structure is untouched either way, so cache keys are stable.
    """
    if np.dtype(mat.data.dtype) == np.dtype(dtype):
        return mat
    return mat.astype(dtype)


def session_or_new(session: Optional["SpGEMMSession"],
                   interpret: Optional[bool]) -> "SpGEMMSession":
    """App-facing helper: create a session honoring ``interpret``, or pass
    an existing one through. A supplied session already fixed its Pallas
    interpret policy at construction, so combining it with an explicit
    ``interpret`` would be silently ignored — refuse instead."""
    if session is None:
        return SpGEMMSession(interpret=interpret)
    if interpret is not None:
        raise ValueError(
            "interpret is fixed when the session is created; construct "
            "SpGEMMSession(interpret=...) instead of passing interpret "
            "alongside an existing session")
    return session


@dataclasses.dataclass(frozen=True, eq=False)
class Call:
    """One prepared multiply (:meth:`SpGEMMSession.prepare`): the validated
    operands, the normalised knobs, and the operands' structure and values
    fingerprints, computed once."""

    a: CSC
    b: CSC
    algorithm: str
    nparts: int
    grid: int
    layers: int
    bs: int
    nblocks: Optional[int]
    chunk: Optional[int]
    semiring: Semiring
    engine: str
    dtype: np.dtype
    struct_fp: Tuple[bytes, bytes]
    val_fp: Tuple[bytes, bytes]

    @property
    def group(self) -> tuple:
        """Calls with equal groups are served by one multiply."""
        return _key(self, self.algorithm, self.engine), self.val_fp


def _key(call: Call, algorithm: str, engine: str) -> tuple:
    """The cache key that serves ``call`` on the rung (algorithm, engine),
    and the one place that decides which knobs split entries. ``nblocks``
    and ``chunk`` are 1D-ring knobs the SUMMA planners lack; a downgraded
    1d rung inherits the 2d/3d call's ``grid*grid`` devices; a 2d rung
    collapses the layers. ``key[1]`` is the geometry the rung plans."""
    if algorithm == "1d":
        geom = (call.nparts if call.algorithm == "1d"
                else call.grid * call.grid,)
        ring = (call.nblocks, call.chunk)
    else:
        geom = (call.grid, call.layers if algorithm == "3d" else 1)
        ring = (None, None)
    return (algorithm, geom, call.bs, *ring, call.semiring.name, engine,
            call.dtype.str, *call.struct_fp)


class _Entry:
    """One cached (plan, executable, device args) triple.

    ``nbytes`` is the device footprint of the entry's argument stacks,
    fixed at compile time (values-only repacks swap same-shape payloads in
    place), plus the slot maps of its device scatter.

    ``scatter`` is the 1D ring's values-only repack on the device
    (:class:`~repro.core.spgemm_1d_device.RingValueScatter`), which writes
    the values ``repack`` hands over into fresh stacks; None for the
    SUMMA engines and for ring plans out of int32's reach, whose
    ``repack`` hands over whole host-refilled stacks.

    ``structure`` builds the plan's structural decode map from the call's
    operands (:func:`~repro.core.spgemm_1d_device.ring_decode_map`, kept
    as ``plan.decode_map``, host memory); None for the SUMMA engines,
    which always scan.
    """

    __slots__ = ("plan", "fn", "args", "decode", "repack", "scatter",
                 "structure", "val_fp", "nbytes")

    def __init__(self, plan, fn, args: List, decode: Callable,
                 repack: Callable, val_fp: Tuple[bytes, bytes],
                 scatter=None, structure: Optional[Callable] = None):
        self.plan = plan
        self.fn = fn
        self.args = args
        self.decode = decode
        self.repack = repack
        self.scatter = scatter
        self.structure = structure
        self.val_fp = val_fp
        self.nbytes = sum(int(getattr(x, "nbytes", 0)) for x in args) \
            + (scatter.nbytes if scatter is not None else 0)

    def release(self) -> None:
        """Drop the device buffer references (the payload/schedule stacks in
        ``args``, the scatter's slot maps), the compiled executables and
        the plan's decode map so eviction actually returns the memory —
        an evicted entry kept alive by a stray reference must not pin its
        arrays."""
        self.args = []
        self.fn = None
        self.repack = None
        self.scatter = None
        if self.structure is not None:
            self.plan.decode_map = None
        self.structure = None


class SpGEMMSession:
    """Persistent SpGEMM session over the device engines (1D/2D/3D).

    ``maxsize`` bounds the LRU entry count (each entry pins a plan, a
    compiled executable and its device-resident payload stacks).
    ``interpret`` forwards to the Pallas launcher (None = auto: interpret
    off-TPU, compiled on TPU).

    ``stats`` carries the cumulative ``device_common.SESSION_STATS``
    surface; ``last_call`` describes the most recent multiply::

        cache_hit      : served from the cache (no host planning)
        repacked       : values-only payload refresh performed
        plan_seconds   : host planning time spent by THIS call (0.0 on hit)
        comm_bytes_planned / comm_bytes_padded / messages / dense_flops :
                         the executed plan's stats surface
        plan_stats     : the executed plan's whole ``stats`` dict (tile,
                         product and slot counts)
        key            : the cache key that served the call
        evicted        : (key, nbytes) of the entries this call's budgets
                         evicted (a cold call only)
        algorithm      : the algorithm rung that actually served the call
        engine         : the engine rung that actually served the call
        requested_algorithm : what the caller asked for (== algorithm
                         unless the ladder downgraded)
        degraded       : served by a rung below the requested one
        retries        : per-stage retry attempts spent by THIS call

    Hardening knobs (all optional; defaults are production-shaped):

    ``validate``        — run :func:`validate_matmul_operands` at ingress.
    ``fault_injector``  — a :class:`runtime.faults.FaultInjector` fired at
                          the top of every stage attempt (tests/chaos).
    ``retry_policy``    — :class:`runtime.RetryPolicy` for per-stage
                          retries (exponential backoff + jitter).
    ``retry_sleep`` / ``retry_rng`` — injectable sleep/jitter source so
                          tier-1 tests never wall-clock-sleep.
    ``breaker_threshold`` — consecutive failures of one cache key before
                          its circuit opens and the rung fails fast.

    ``max_bytes``       — global LRU byte budget (default off) over cached
                          entries' device argument stacks
                          (``stats["bytes_cached"]``); oldest entries are
                          evicted until the budget holds, keeping at least
                          the newest so an oversized multiply still serves.
    """

    def __init__(self, maxsize: int = 32,
                 interpret: Optional[bool] = None, *,
                 validate: bool = True,
                 fault_injector=None,
                 retry_policy: Optional[RetryPolicy] = None,
                 retry_sleep: Callable[[float], None] = time.sleep,
                 retry_rng: Optional[np.random.Generator] = None,
                 breaker_threshold: int = 3,
                 max_bytes: Optional[int] = None):
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        if breaker_threshold < 1:
            raise ValueError(f"breaker_threshold must be >= 1, "
                             f"got {breaker_threshold}")
        if max_bytes is not None and max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1 or None, "
                             f"got {max_bytes}")
        self.maxsize = maxsize
        self.max_bytes = max_bytes
        self.interpret = interpret
        self.validate = validate
        self.fault_injector = fault_injector
        self.retry_policy = retry_policy if retry_policy is not None else \
            RetryPolicy(max_retries=2, backoff_s=0.05, backoff_mult=2.0,
                        jitter=0.25)
        self._retry_sleep = retry_sleep
        self._retry_rng = retry_rng
        self.breaker_threshold = breaker_threshold
        self._cache: "OrderedDict[tuple, _Entry]" = OrderedDict()
        # loop-invariant-operand blockize reuse inside the 1D planner (BC
        # re-plans the same adjacency against a fresh frontier every level)
        self._blockize_cache: dict = {}
        # circuit breaker: cache key -> consecutive stage failures; reset
        # on the first success, opened at breaker_threshold
        self._quarantine: dict = {}
        self.stats = {k: 0 for k in SESSION_STATS}
        self.stats["plan_seconds_saved"] = 0.0
        self.last_call: dict = {}

    # ---- internals --------------------------------------------------------

    def _count_trace(self):
        self.stats["traces"] += 1

    def _on_retry(self, attempt: int, exc: Exception) -> None:
        self.stats["retries"] += 1

    def _stage(self, stage: str, thunk: Callable, context: dict):
        """Run one pipeline stage: fault-injection point + retry/backoff,
        wrapping whatever survives retries into the stage's typed error."""

        def attempt():
            if self.fault_injector is not None:
                self.fault_injector.fire(stage)
            return thunk()

        try:
            return with_retries(attempt, self.retry_policy,
                                on_retry=self._on_retry,
                                sleep=self._retry_sleep,
                                rng=self._retry_rng)()
        except Exception as e:
            raise wrap_stage_error(stage, e, context) from e

    def _record_failure(self, key: tuple) -> None:
        """A rung failed on ``key``: bump its breaker count and quarantine
        any cached entry (drop + release buffers) so a poisoned
        plan/executable can never serve a later call."""
        self._quarantine[key] = self._quarantine.get(key, 0) + 1
        entry = self._cache.pop(key, None)
        if entry is not None:
            self.stats["bytes_cached"] -= entry.nbytes
            entry.release()
            self.stats["quarantined"] += 1

    def _enforce_budgets(self) -> List[Tuple[tuple, int]]:
        """Evict LRU-first until the entry count, then the byte budget,
        holds; returns the evicted (key, nbytes). The byte budget always
        keeps the newest entry — a single multiply larger than the budget
        still serves (and is evicted by whatever lands next), it just
        can't pin neighbours."""
        evicted = []
        while len(self._cache) > self.maxsize or (
                self.max_bytes is not None and len(self._cache) > 1
                and self.stats["bytes_cached"] > self.max_bytes):
            key = next(iter(self._cache))
            evicted.append((key, self._cache[key].nbytes))
            self.evict(key)
        return evicted

    def _plan(self, call: Call, algorithm: str, geom: tuple):
        """Host planning only (the ``plan`` stage) at the rung's geometry;
        returns (plan, decode, repack, structure)."""
        from .spgemm_1d_device import (build_device_plan, decode_ring_output,
                                       repack_ring_payloads, ring_decode_map)
        from .spgemm_2d_device import (build_summa_plan, decode_summa_output,
                                       repack_summa_payloads)

        with span("spgemm.session.plan"):
            if algorithm == "1d":
                plan = build_device_plan(
                    call.a, call.b, geom[0], bs=call.bs, nblocks=call.nblocks,
                    dtype=call.dtype, semiring=call.semiring,
                    a_blockize_cache=self._blockize_cache, chunk=call.chunk)
                return (plan, decode_ring_output, repack_ring_payloads,
                        ring_decode_map)
            plan = build_summa_plan(
                call.a, call.b, grid=geom[0], layers=geom[1], bs=call.bs,
                dtype=call.dtype, semiring=call.semiring)
            return plan, decode_summa_output, repack_summa_payloads, None

    def _compile(self, plan, algorithm: str, engine: str):
        """Place the plan and trace + lower + compile the shard_map body
        ahead of time (the ``compile`` stage), and for the 1D ring its
        values-only scatter; returns (compiled executable, device args,
        scatter or None). A failure of the program itself raises
        :class:`CompileError`, which is neither retried nor laddered."""
        from .spgemm_1d_device import compile_ring, compile_ring_scatter
        from .spgemm_2d_device import compile_summa

        compiler = compile_ring if algorithm == "1d" else compile_summa
        with span("spgemm.session.compile"):
            fn, args = compiler(plan, engine=engine,
                                interpret=self.interpret,
                                trace_probe=self._count_trace)
            try:
                compiled = fn.lower(*args).compile()
                scatter = compile_ring_scatter(plan, args[0].sharding) \
                    if algorithm == "1d" else None
            except Exception as e:
                raise CompileError(f"{type(e).__name__}: {e}",
                                   stage="compile",
                                   context={"algorithm": algorithm,
                                            "engine": engine}) from e
        return compiled, list(args), scatter

    # ---- the multiply: prepare, then run ----------------------------------

    def prepare(self, a: CSC, b: CSC, *, algorithm: str = "1d",
                nparts: int = 1, grid: int = 1, layers: int = 1,
                bs: int = 32, nblocks: Optional[int] = None,
                semiring: Semiring = PLUS_TIMES, engine: str = "auto",
                dtype=np.float32, chunk: Optional[int] = None) -> Call:
        """Check the knobs, validate the operands and fingerprint them:
        the :class:`Call` that :meth:`run` serves.

        ``algorithm`` selects the distributed engine: ``"1d"`` (the
        sparsity-aware ring, geometry ``nparts``), ``"2d"`` (sparse SUMMA,
        geometry ``grid``×``grid``) or ``"3d"`` (Split-3D, geometry
        ``grid``×``grid``×``layers``). The geometry must fit the visible
        device count, exactly as for the direct ``run_device_*`` calls.

        ``chunk`` selects the 1D ring's double-buffered k-chunk pipeline
        (ring steps per fetched chunk; ``None`` = legacy single-pass
        ring). It is part of the cache key — chunked and unchunked plans
        compile different bodies — and is ignored by the 2d/3d engines,
        exactly like ``nblocks``.
        """
        if algorithm not in ALGORITHMS:
            raise ValueError(
                f"algorithm must be one of {ALGORITHMS}, got {algorithm!r}")
        if chunk is not None and (not isinstance(chunk, int) or chunk < 1):
            raise ValueError(
                f"chunk must be a positive int or None, got {chunk!r}")
        if self.validate:
            try:
                with span("spgemm.session.validate",
                          nnz=_stored_entries(a, b)):
                    validate_matmul_operands(a, b, semiring=semiring)
            except ValidationError:
                self.stats["validation_failures"] += 1
                raise
        with span("spgemm.session.fingerprint", hashed_bytes=sum(
                16 + m.indptr.nbytes + m.indices.nbytes + m.data.nbytes
                for m in (a, b))):
            struct_fp = (structure_fingerprint(a), structure_fingerprint(b))
            val_fp = (values_fingerprint(a), values_fingerprint(b))
        return Call(a, b, algorithm, nparts, grid, layers, bs, nblocks,
                    chunk, semiring, resolve_engine(engine), np.dtype(dtype),
                    struct_fp, val_fp)

    def run(self, call: Call) -> CSC:
        """C = A ⊗ B on the device path for a prepared call, cached by
        structure, down the degradation ladder."""
        self.stats["calls"] += 1
        # the degradation ladder: engine fallback inside each algorithm
        # rung, then algorithm downgrade. Every rung is bitwise
        # oracle-equivalent, so descending trades comm volume for service.
        rungs = []
        for alg in DOWNGRADE[call.algorithm]:
            rungs.append((alg, call.engine))
            if call.engine == "pallas":
                rungs.append((alg, "jnp"))

        retries_before = self.stats["retries"]
        last_err: Optional[SpGEMMError] = None
        for i, (alg_r, eng_r) in enumerate(rungs):
            key = _key(call, alg_r, eng_r)
            try:
                c, info = self._run_rung(call, key, alg_r, eng_r)
            except (ValidationError, CompileError):
                # an ingress rejection (e.g. a dtype-mismatched values-only
                # repack) is deterministic: every rung would refuse it the
                # same way — and a colder rung would *accept* it by planning
                # fresh with the silent cast the rejection exists to stop.
                # A program that does not compile is a bug a lower rung
                # would hide. The ladder is for device faults, not for
                # bad requests or broken programs.
                raise
            except SpGEMMError as e:
                last_err = e
                if i + 1 < len(rungs):
                    self.stats["fallbacks"] += 1
                continue
            s = info["plan_stats"]
            self.last_call = dict(
                cache_hit=info["cache_hit"], repacked=info["repacked"],
                key=key, evicted=info["evicted"],
                algorithm=alg_r, engine=eng_r,
                requested_algorithm=call.algorithm, degraded=i > 0,
                retries=self.stats["retries"] - retries_before,
                plan_seconds=info["plan_seconds"],
                comm_bytes_planned=s["comm_bytes_planned"],
                comm_bytes_padded=s["comm_bytes_padded"],
                messages=s["messages"], dense_flops=s["dense_flops"],
                plan_stats=dict(s))
            return c
        raise last_err

    def matmul(self, a: CSC, b: CSC, **knobs) -> CSC:
        """C = A ⊗ B on the device path, cached by structure: ``run`` of
        ``prepare`` (which documents the knobs)."""
        return self.run(self.prepare(a, b, **knobs))

    def _run_rung(self, call: Call, key: tuple, algorithm: str,
                  engine: str) -> Tuple[CSC, dict]:
        """One rung of the ladder: serve the call from the entry ``key``
        with a fixed (algorithm, engine), all four stages under retry +
        typed wrapping."""
        a, b, val_fp, dtype = call.a, call.b, call.val_fp, call.dtype
        ctx = {"algorithm": algorithm, "engine": engine,
               "requested_algorithm": call.algorithm}
        failures = self._quarantine.get(key, 0)
        if failures >= self.breaker_threshold:
            raise DeviceExecError(
                "circuit breaker open: this plan-cache key failed "
                f"{failures} consecutive times", stage="execute",
                context=ctx)

        entry = self._cache.get(key)
        hit = entry is not None
        repacked = False
        plan_seconds = 0.0
        try:
            if hit:
                # the operands whose values changed since the entry ran
                sides = tuple(m if val_fp[i] != entry.val_fp[i] else None
                              for i, m in enumerate((a, b)))
                # values-only repacks blockize straight into the plan's
                # payload stacks; a dtype-mismatched operand would be cast
                # silently (float64 values narrowed into a float32-keyed
                # entry) and still count as a cache hit — reject at
                # ingress instead, before anything mutates
                mism = [f"operand {nm} has data dtype "
                        f"{np.dtype(m.data.dtype).name}"
                        for nm, m in zip("ab", sides)
                        if m is not None and np.dtype(m.data.dtype) != dtype]
                if mism:
                    self.stats["validation_failures"] += 1
                    raise ValidationError(
                        "dtype-mismatched values-only repack: "
                        + "; ".join(mism)
                        + f" but the cached plan's payloads are "
                        f"{dtype.name} — repacking would silently narrow "
                        "the values; cast the operand or request a "
                        "matching dtype=", stage="repack", context=ctx)
                self._cache.move_to_end(key)
                self.stats["plan_cache_hits"] += 1
                self.stats["plan_seconds_saved"] += \
                    entry.plan.stats["plan_seconds"]
                if val_fp != entry.val_fp:
                    # values-only path: refill payload stacks, keep the
                    # plan, the schedules and the compiled executable — and
                    # only for the side(s) whose values actually changed
                    # (BC's backward sweep keeps the adjacency operand
                    # bit-identical while the frontier moves every level).
                    # The 1D ring's repack hands over only the values,
                    # which its scatter writes into fresh stacks on the
                    # device; the SUMMA engines (and a ring plan past
                    # int32's reach) hand over whole host-refilled stacks,
                    # put as they are. The stacks are swapped in only once
                    # all are placed, and a failure quarantines the entry,
                    # so a half-swapped payload stack can never serve a
                    # call.
                    def do_repack():
                        scatter = entry.scatter
                        with span("spgemm.repack.blockize",
                                  tiles=_tile_slots(entry.plan, *sides)):
                            host = entry.repack(entry.plan, *sides)
                        with span("spgemm.repack.h2d",
                                  h2d_bytes=sum(x.nbytes for x in host
                                                if x is not None),
                                  entries=0 if scatter is None
                                  else _stored_entries(*(
                                      m for m, x in zip(sides, host)
                                      if x is not None))):
                            if scatter is None:
                                import jax
                                placed = tuple(
                                    None if x is None else jax.device_put(
                                        x, entry.args[i].sharding)
                                    for i, x in enumerate(host))
                            else:
                                placed = scatter(host)
                        for i, x in enumerate(placed):
                            if x is not None:
                                entry.args[i] = x

                    self._stage("repack", do_repack, ctx)
                    entry.val_fp = val_fp
                    self.stats["payload_repacks"] += 1
                    repacked = True
            else:
                t0 = time.perf_counter()
                plan, decode, repack, structure = self._stage(
                    "plan",
                    lambda: self._plan(call, algorithm, key[1]), ctx)
                fn, args, scatter = self._stage(
                    "compile",
                    lambda: self._compile(plan, algorithm, engine), ctx)
                plan_seconds = time.perf_counter() - t0
                entry = _Entry(plan, fn, args, decode, repack, val_fp,
                               scatter=scatter, structure=structure)

            def do_execute():
                with span("spgemm.execute.dispatch",
                          mxu_passes=call.semiring.mxu_passes):
                    res = entry.fn(*entry.args)
                if hit and entry.structure is not None \
                        and entry.plan.decode_map is None:
                    # the plan's second use: its structure repeats, so
                    # build the decode map, while the device runs
                    entry.plan.decode_map = entry.structure(entry.plan,
                                                            a, b)
                with span("spgemm.execute.fetch", d2h_bytes=res.nbytes):
                    out = np.asarray(res)
                return entry.decode(entry.plan, out)

            c = self._stage("execute", do_execute, ctx)
        except SpGEMMError as e:
            # an ingress rejection of a malformed request leaves the cached
            # entry healthy and untouched — quarantining it (or bumping its
            # breaker) would punish the cache for the caller's operand. A
            # compile failure left nothing cached, and an open breaker
            # would turn it into a laddered DeviceExecError on the next call
            if not isinstance(e, (ValidationError, CompileError)):
                self._record_failure(key)
            raise
        # success: only now may a cold entry enter the cache — a plan that
        # never executed cleanly is never cached, so injected faults can't
        # poison it — and the key's breaker resets
        evicted = []
        if not hit:
            self.stats["plan_cache_misses"] += 1
            self._cache[key] = entry
            self.stats["bytes_cached"] += entry.nbytes
            evicted = self._enforce_budgets()
        self._quarantine.pop(key, None)
        return c, dict(cache_hit=hit, repacked=repacked, evicted=evicted,
                       plan_seconds=plan_seconds,
                       plan_stats=entry.plan.stats)

    # ---- maintenance ------------------------------------------------------

    def __len__(self) -> int:
        return len(self._cache)

    def clear(self) -> None:
        """Drop every cached plan/executable, releasing the device buffer
        references each entry pinned (stats are kept; breakers reset)."""
        for entry in self._cache.values():
            entry.release()
        self._cache.clear()
        self._blockize_cache.clear()
        self._quarantine.clear()
        self.stats["bytes_cached"] = 0

    def cached_bytes(self) -> int:
        """Device bytes pinned by cached entries."""
        return int(self.stats["bytes_cached"])

    def cached_entries(self) -> int:
        """Cached entry count."""
        return len(self._cache)

    def entries(self) -> List[Tuple[tuple, int]]:
        """(key, nbytes) of every cached entry, least recently used
        first."""
        return [(k, e.nbytes) for k, e in self._cache.items()]

    def evict(self, key: tuple) -> None:
        """Evict one cached entry: release its device buffers and settle
        the byte ledger (counted in ``stats["evictions"]``)."""
        entry = self._cache.pop(key)
        self.stats["evictions"] += 1
        self.stats["bytes_cached"] -= entry.nbytes
        entry.release()
