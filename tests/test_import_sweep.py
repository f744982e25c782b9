"""Import every ``repro.*`` module — the API-drift tripwire.

JAX renames public APIs between minor releases (``jax.shard_map``,
``pltpu.TPUCompilerParams`` → ``CompilerParams``, ...). Call sites resolve
those names through ``repro.compat``, and this sweep makes the next rename
fail loudly at test-collection time — one red test per broken module —
instead of deep inside a subprocess-spawned assertion where the traceback
is a truncated stderr string. (Statically, ``tools/replint`` rule RS002
forbids spelling a drifting name outside compat.py in the first place;
this sweep is the runtime half of that contract.)
"""

import importlib
import os
import pkgutil
from pathlib import Path

import pytest

SRC_ROOT = Path(__file__).resolve().parents[1] / "src" / "repro"


def _all_modules():
    """Every ``repro.*`` module, derived from the ``src/repro`` file tree.

    Filesystem-derived (not ``pkgutil``-only) so the sweep cannot silently
    rot: a new subpackage missing its ``__init__.py`` — which
    ``walk_packages`` would skip without a sound — still produces a
    parametrized case here, and fails it loudly.
    """
    names = ["repro"]
    for p in sorted(SRC_ROOT.rglob("*.py")):
        parts = p.relative_to(SRC_ROOT.parent).with_suffix("").parts
        if parts[-1] == "__init__":
            parts = parts[:-1]
        names.append(".".join(parts))
    return sorted(set(names))


def test_tree_matches_pkgutil_walk():
    """Every module the filesystem sweep finds is reachable by a plain
    package walk too — i.e. no orphan .py file sits outside the package
    graph (missing ``__init__.py`` in an ancestor directory)."""
    pkg = importlib.import_module("repro")
    walked = {"repro"} | {info.name for info in pkgutil.walk_packages(
        pkg.__path__, prefix="repro.")}
    missing = set(_all_modules()) - walked
    assert not missing, \
        f"modules on disk but invisible to the import system: {missing}"


@pytest.mark.parametrize("name", _all_modules())
def test_module_imports(name):
    # repro.launch.dryrun mutates XLA_FLAGS at import (deliberately, for its
    # 512-device dry-run meshes); keep the sweep side-effect-free so later
    # subprocess-spawning tests inherit a clean environment.
    env_before = dict(os.environ)
    try:
        importlib.import_module(name)
    finally:
        os.environ.clear()
        os.environ.update(env_before)


def test_compat_is_the_only_drift_point():
    """The resolved shims exist and are callable — the contract every
    migrated call site relies on."""
    from repro import compat

    assert callable(compat.shard_map)
    params = compat.tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")
    assert "--xla_force_host_platform_device_count=8" \
        == compat.host_device_count_flag(8)
    mesh = compat.cpu_device_mesh(1, axis="p")
    assert mesh.shape["p"] == 1
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        compat.cpu_device_mesh(10_000)


@pytest.mark.parametrize("backend,names_flag", [("cpu", True),
                                                ("tpu", False)])
def test_too_few_devices_names_the_flag_only_on_cpu(monkeypatch, backend,
                                                    names_flag):
    """Only the CPU backend can fake devices: on a chip the error must not
    send the user to the host-device flag."""
    from repro import compat

    monkeypatch.setattr(compat.jax, "default_backend", lambda: backend)
    msg = compat.too_few_devices(4, 1)
    assert msg.startswith(f"need 4 devices, have 1 ({backend})")
    assert ("xla_force_host_platform" in msg) is names_flag
