"""JAX API shims — the single point where drifting JAX names are spelled.

Supported JAX: **0.9.0** (jax, jaxlib; libtpu 0.0.34 on the chip — see
``requirements.txt``). JAX renames and relocates public APIs between minor
releases (``shard_map`` moved to ``jax.shard_map`` and its ``check_rep``
knob became ``check_vma``; Pallas-TPU renamed ``TPUCompilerParams`` to
``CompilerParams``), and a codebase that spells such a name at every call
site breaks wholesale on every move.

Policy: resolve each drifting symbol **once, here**, for the one installed
JAX. Everything else in the repo imports from ``repro.compat`` and never
references the ``jax.*`` spelling directly (replint RS002;
``tests/test_import_sweep.py`` imports every ``repro.*`` module so the
next rename fails loudly at collection time instead of deep inside a
subprocess assertion). When the pin moves: repair the resolver below,
keep call sites unchanged, and note the change in ROADMAP.md.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

__all__ = ["shard_map", "tpu_compiler_params", "make_mesh",
           "cpu_device_mesh", "host_device_count_flag", "too_few_devices"]


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def shard_map(f, *, mesh, in_specs, out_specs, check_rep: bool = True):
    """``jax.shard_map`` with the call sites' ``check_rep`` spelling.

    ``check_rep=False`` maps to ``check_vma=False``. Pass it at call sites
    whose traced body holds a ``pallas_call``: the varying-manual-axes
    checker needs a ``vma`` on every ``out_shape``, which a kernel's
    ``jax.ShapeDtypeStruct`` does not carry, and tracing then fails with
    "``vma`` on ``jax.ShapeDtypeStruct`` must not be ``None``". Everywhere
    else keep the checker on — it catches out_specs that claim
    replication that was never established.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types.

    ``jax.make_mesh`` now defaults to Explicit axes, which
    ``with_sharding_constraint`` rejects; the model stack constrains
    activations by ``PartitionSpec`` and needs the Auto (GSPMD) axes.
    """
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kwargs)


# ---------------------------------------------------------------------------
# Pallas-TPU compiler params
# ---------------------------------------------------------------------------

def tpu_compiler_params(*, dimension_semantics: Optional[Sequence[str]] = None,
                        **kwargs):
    """Build the Pallas-TPU compiler-params struct.

    ``dimension_semantics`` is the tuple of per-grid-axis annotations
    ("parallel" / "arbitrary") every kernel in this repo passes; further
    fields (``vmem_limit_bytes``, ...) forward unchanged.
    """
    if dimension_semantics is not None:
        kwargs["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kwargs)


# ---------------------------------------------------------------------------
# host-platform device ring (fake multi-device CPU meshes)
# ---------------------------------------------------------------------------

def host_device_count_flag(n: int) -> str:
    """The XLA flag that fakes ``n`` host devices (must be set in the
    environment before the first jax backend initialisation)."""
    return f"--xla_force_host_platform_device_count={n}"


def cpu_device_mesh(n: int, axis: str = "p") -> Mesh:
    """A 1D ``Mesh`` over the first ``n`` visible devices.

    This is the ring-setup used by the shard_map SpGEMM executor and the
    multi-device subprocess tests. Raises when the process sees fewer
    devices than requested (see :func:`too_few_devices`).
    """
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(too_few_devices(n, len(devs)))
    return Mesh(np.array(devs[:n]), (axis,))


def too_few_devices(need: int, have: int, what: str = "") -> str:
    """The error text for a mesh that needs more devices than are visible.

    Only the CPU backend can fake devices, so only there does the text
    name the XLA flag to relaunch with; on an accelerator the fix is a
    smaller geometry or a larger slice."""
    msg = f"need {need} devices{what}, have {have} " \
          f"({jax.default_backend()})"
    if jax.default_backend() == "cpu":
        msg += (f"; relaunch with XLA_FLAGS={host_device_count_flag(need)} "
                "in the environment (jax locks the device count at first "
                "init)")
    return msg
