"""replint configuration — the repo-specific scopes, allowlists and
registries the rules consume.

Paths are repo-root-relative POSIX globs, matched with ``fnmatch`` against
the path of each linted file (relative to ``--root``, default cwd). Two
kinds of path sets exist:

  * ``*_SCOPE``  — the rule ONLY runs on matching files (everything else
    is silently out of scope);
  * ``*_ALLOW``  — the rule runs everywhere EXCEPT matching files (the
    sanctioned home of the pattern it polices).

Keeping this in one module means a new engine/app/test directory is a
one-line config change, not a rule rewrite.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# RS001 — raw pl.pallas_call: only the unified launcher may spell it
# ---------------------------------------------------------------------------
RS001_ALLOW = ("src/repro/kernels/launch.py",)

# ---------------------------------------------------------------------------
# RS002 — drifting JAX API names resolve in compat.py, nowhere else
# ---------------------------------------------------------------------------
RS002_ALLOW = ("src/repro/compat.py",)

# Names that have moved between supported JAX releases. Importing them
# from a ``jax*`` module, or spelling them as an attribute, couples a call
# site to one release.
DRIFTING_JAX_IMPORTS = frozenset({
    "shard_map", "TPUCompilerParams", "CompilerParams",
})
DRIFTING_JAX_ATTRS = frozenset({"TPUCompilerParams", "CompilerParams"})

# The compat shims themselves: redefining one outside compat.py forks the
# single drift point.
COMPAT_SHIM_NAMES = frozenset({
    "shard_map", "tpu_compiler_params", "cpu_device_mesh",
})

# ---------------------------------------------------------------------------
# RS003 — semiring identity: device-engine modules must not zero-fill
# ---------------------------------------------------------------------------
RS003_SCOPE = (
    "src/repro/core/*_device.py",
    "src/repro/core/device_common.py",
    "src/repro/kernels/bsr_spgemm/*.py",
)

# dtype spellings that mark an array as index/flag metadata, where a
# literal zero is a coordinate, not an additive identity.
INTEGRAL_DTYPE_NAMES = frozenset({
    "bool", "bool_", "int8", "int16", "int32", "int64", "intp", "int_",
    "uint8", "uint16", "uint32", "uint64", "integer",
})

ZEROS_CALLEES = frozenset({"zeros", "zeros_like"})
FULL_CALLEES = frozenset({"full", "full_like"})

# ---------------------------------------------------------------------------
# RS004 — the app/serve layer multiplies through SpGEMMSession only
# ---------------------------------------------------------------------------
RS004_SCOPE = (
    "src/repro/apps/*.py",
    "src/repro/serve/*.py",
    "src/repro/launch/serve.py",
    "src/repro/launch/serve_spgemm.py",
)

SESSION_ONLY_NAMES = frozenset({
    "build_device_plan", "build_summa_plan", "build_summa3d_plan",
    "compile_ring", "ring_program", "compile_summa", "compile_summa3d",
})

# ---------------------------------------------------------------------------
# RS005 — vectorized-planner registry: these hot functions must not fall
# back to Python loops over nnz/tile-sized iterables (O(P)/O(P²) loops
# over devices or ring steps with vectorized bodies are fine and common).
# ---------------------------------------------------------------------------
PLANNER_HOT_FUNCTIONS = frozenset({
    # 1D ring planning / decode (core/spgemm_1d_device.py)
    "payload_need_maps", "build_device_plan", "repack_ring_payloads",
    "decode_ring_output", "segment_ring_schedule",
    # 2D/3D planning / decode (core/spgemm_2d_device.py, _3d_device.py)
    "build_summa_plan", "repack_summa_payloads", "decode_summa_output",
    # shared packing/decode (core/device_common.py)
    "pack_schedules", "decode_tiles",
    # blockize + symbolic schedule (core/blocksparse.py)
    "from_csc", "build_schedule",
})

# Attributes whose length is O(nnz) or O(ntiles): iterating one of these
# in Python inside a hot function is the exact regression PR 2 removed.
NNZ_SIZED_ATTRS = frozenset({
    "indices", "indptr", "data", "tile_rows", "tile_cols", "nzc_ids",
})

# Name suffixes that mark a zip() operand as an nnz-sized coordinate
# array (the ``zip(rows, cols)`` idiom).
NNZ_SIZED_NAME_SUFFIXES = ("rows", "cols", "vals", "slots", "indices")

# ---------------------------------------------------------------------------
# RS006 — interpret literals: tests may pin, product code must auto
# ---------------------------------------------------------------------------
RS006_ALLOW = ("tests/*.py", "tests/**/*.py")

# ---------------------------------------------------------------------------
# RS007 — hypothesis is uninstallable here; no allowlist at all
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# RS008 — swallowed exceptions in the hardened core/runtime layers: a bare
# `except:` / `except Exception:` / `except BaseException:` handler that
# never re-raises hides the failure from the session's typed-error ladder
# (wrap via core.validate.wrap_stage_error or re-raise instead).
# ---------------------------------------------------------------------------
RS008_SCOPE = (
    "src/repro/core/*.py",
    "src/repro/core/**/*.py",
    "src/repro/runtime/*.py",
)

# exception names considered catch-alls when named in an except clause
CATCH_ALL_EXC_NAMES = frozenset({"Exception", "BaseException"})

# ---------------------------------------------------------------------------
# flow rules (RS010–RS015) — interprocedural layer, tools/replint/flow/
# ---------------------------------------------------------------------------

# Mesh constructors the context visitor understands:
#   ctor name -> (axes arg position, axes kwarg name, implicit default)
# `cpu_device_mesh(n, axis="p")` declares one axis (default "p");
# `device_grid_mesh(shape, axes)` / raw `Mesh(devices, axes)` declare a
# tuple of axes with no default.
MESH_CONSTRUCTORS = {
    "cpu_device_mesh": (1, "axis", "p"),
    "device_grid_mesh": (1, "axes", None),
    "Mesh": (1, "axes", None),
}

# RS010 — collectives whose axis argument must name a declared mesh axis:
#   callee terminal name -> positional index of the axis argument
# (the kwarg spellings `axis_name` / `axis` are also recognized).
COLLECTIVE_AXIS_ARG = {
    "ppermute": 1,
    "all_gather": 1,
    "all_to_all": 1,
    "psum": 1,
    "pmax": 1,
    "pmin": 1,
    "pmean": 1,
    "axis_index": 0,
    "jnp_axis_reduce": 1,
}

# RS012 — method calls that force a host-device sync when the receiver is
# a tracer, and numpy leaves that are pure metadata (never touch device
# buffers) and therefore stay legal inside traced code.
RS012_SYNC_METHODS = frozenset({"item", "block_until_ready"})
RS012_TRACE_SAFE_NUMPY = frozenset({"dtype", "iinfo", "finfo"})

# RS013 — keyword names that put their value in a semiring-identity
# position, and the call-graph depth the taint summaries explore.
RS013_FILL_KWARGS = frozenset({"fill", "fill_value", "constant_values"})
RS013_MAX_DEPTH = 3

# RS014 — callables whose function argument gets trace-compiled (and so
# bakes its closure into the executable cache key). Tests are exempt:
# pinning a one-shot jit there is a legitimate idiom.
RS014_COMPILE_TARGETS = frozenset({
    "jit", "shard_map", "compile_ring", "compile_summa", "compile_summa3d",
})
RS014_ALLOW = ("tests/*.py", "tests/**/*.py")

# RS015 — device plan builders must assign the full shared stats surface
# on every return path. The authoritative key list is read from
# `device_common.REQUIRED_STATS` in the linted program itself; the
# fallback below only applies when that module is not part of the lint
# set (e.g. single-file fixtures).
RS015_SCOPE = ("src/repro/core/*_device.py",)
RS015_BUILDER_GLOB = "build_*_plan"
DEVICE_COMMON_MODULE = "repro.core.device_common"
REQUIRED_STATS_FALLBACK = (
    "comm_bytes_planned", "comm_bytes_padded", "messages",
    "dense_flops", "plan_seconds",
    "peak_payload_tiles", "chunks", "overlap_fraction",
)
