"""The SpGEMM product path compiles for a TPU v5e, without one.

JAX ships the TPU compiler, and it compiles for a *described* topology
(``v5e:2x2``) that is not attached. What interpret mode cannot see — scalar
memory (SMEM) overflow of the prefetched schedule, primitives the Pallas TPU
lowering lacks, collectives on a 4-chip mesh — it refuses here, at no chip
time:

  * ``bsr_spgemm_pallas`` at ``bs=128`` for each semiring;
  * a schedule far longer than one launch window (the real hv15r-like A²
    schedules 367,218 tile products), and the one-launch form of a long
    schedule, which overflows SMEM;
  * the 1D ring's jitted shard_map body on a mesh of 4 described chips,
    given shapes only (``ring_program`` places nothing).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest-xdist workers import every
test file. The last test runs on the CPU: a session that cannot compile its
program raises a typed error instead of serving the call on a lower rung.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import (BOOL_OR_AND, CSC, MIN_PLUS, PLUS_TIMES,
                        banded_clustered, spgemm)
from repro.core import spgemm_1d_device
from repro.core.session import SpGEMMSession
from repro.core.spgemm_1d_device import (build_device_plan, ring_args,
                                         ring_program)
from repro.core.validate import CompileError
from repro.kernels.bsr_spgemm.kernel import (SCHEDULE_WINDOW,
                                             bsr_spgemm_pallas)

BS = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(one_chip, *, nprod, na, nc, semiring=PLUS_TIMES,
                    window=SCHEDULE_WINDOW):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(a, b, a_slot, b_slot, c_slot, flags):
        return bsr_spgemm_pallas(a, b, a_slot, b_slot, c_slot, flags,
                                 nprod=nprod, nc=nc, bs=BS, interpret=False,
                                 semiring=semiring, window=window)

    args = [sds((na, BS, BS), jnp.float32)] * 2 \
        + [sds((nprod,), jnp.int32)] * 4
    return jax.jit(run).lower(*args).compile()


@pytest.mark.parametrize("semiring", [PLUS_TIMES, BOOL_OR_AND, MIN_PLUS],
                         ids=lambda s: s.name)
def test_kernel_compiles_for_v5e(one_chip, semiring):
    """One launch window at the MXU's tile width, for every semiring
    (min-plus runs on the VPU and has no lane dynamic_slice to lower)."""
    compiled = _compile_kernel(one_chip, nprod=4096, na=512, nc=513,
                               semiring=semiring)
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_long_schedule_compiles_in_windows(one_chip):
    """The real hv15r-like A² schedule (367,218 products, 19,377 payload
    tiles, 38,279 output tiles plus the garbage slot) compiles as one
    launch per window, in place: no extra output-sized temporary."""
    nprod = 367_218
    compiled = _compile_kernel(one_chip, nprod=nprod, na=19_377, nc=38_280)
    assert nprod > 4 * SCHEDULE_WINDOW
    n_windows = -(-nprod // SCHEDULE_WINDOW)
    assert compiled.as_text().count("tpu_custom_call") == n_windows
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_one_launch_schedule_overflows_smem(one_chip):
    """Why the windows exist: 65,536 products in one launch prefetch
    1 MiB of schedule, all of a v5e core's scalar memory."""
    with pytest.raises(Exception, match="(?i)smem"):
        _compile_kernel(one_chip, nprod=65_536, na=512, nc=513,
                        window=65_536)


@pytest.mark.parametrize("chunk", [None, 1], ids=["single_pass", "chunked"])
def test_ring_compiles_on_4_described_chips(topo, chunk):
    """The 1D ring's shard_map body on a 4-chip mesh: a kernel per device
    and a collective permute per ring step, from shapes alone."""
    a = banded_clustered(8192, 1024, 16, seed=0).astype(np.float32)
    plan = build_device_plan(a, a, nparts=4, bs=BS, chunk=chunk)
    assert sum(plan.step_sizes) > 0          # the parts fetch payloads
    mesh = Mesh(np.array(topo.devices[:4]), ("p",))
    fn = ring_program(plan, mesh, interpret=False)
    shard = NamedSharding(mesh, P("p"))
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard)
              for x in ring_args(plan)]
    text = fn.lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_served_ring_names_its_kernel_launches(topo):
    """The served ring program names each kernel launch itself
    (``pallas_call(name=...)``), so every launch compiles to an op
    ``bsr_spgemm_pallas.<k>``: the op name a device trace shows, whatever
    jitted function encloses the kernel."""
    a = banded_clustered(4096, 512, 16, seed=0).astype(np.float32)
    plan = build_device_plan(a, a, nparts=1, bs=BS, semiring=MIN_PLUS)
    mesh = Mesh(np.array(topo.devices[:1]), ("p",))
    fn = ring_program(plan, mesh, interpret=False)
    shard = NamedSharding(mesh, P("p"))
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard)
              for x in ring_args(plan)]
    lowered = fn.lower(*shapes)
    launches = lowered.as_text().count("@tpu_custom_call")
    assert launches >= 1
    assert lowered.as_text().count(
        'kernel_name = "bsr_spgemm_pallas"') == launches
    ops = [line.split(" = ", 1)[0].split()[-1].lstrip("%")
           for line in lowered.compile().as_text().splitlines()
           if 'custom_call_target="tpu_custom_call"' in line]
    assert len(ops) == launches
    assert all(re.fullmatch(r"bsr_spgemm_pallas(\.\d+)*", op) for op in ops)


def test_session_raises_on_a_program_that_does_not_compile(monkeypatch):
    """A refused kernel is a CompileError at once: no retry, no pallas→jnp
    rung, nothing cached, no breaker count — the same call with the jnp
    engine asked for explicitly still serves."""
    real = spgemm_1d_device.run_schedule
    engines = []

    def refusing(*args, engine, **kw):
        engines.append(engine)
        if engine == "pallas":
            raise NotImplementedError(
                "Unimplemented primitive in Pallas TPU lowering: "
                "dynamic_slice")
        return real(*args, engine=engine, **kw)

    monkeypatch.setattr(spgemm_1d_device, "run_schedule", refusing)
    a = banded_clustered(96, 8, 4.0, seed=3)
    # integer values: every engine's sums are exact, so results compare bitwise
    a = CSC(a.indptr, a.indices,
            (1 + np.arange(a.nnz) % 4).astype(np.float32), a.shape)
    s = SpGEMMSession(retry_sleep=lambda _: None)
    with pytest.raises(CompileError, match="dynamic_slice") as err:
        s.matmul(a, a, bs=16)
    assert err.value.stage == "compile"
    assert engines == ["pallas"]
    assert (s.stats["retries"], s.stats["fallbacks"]) == (0, 0)
    assert len(s) == 0 and not s._quarantine

    c = s.matmul(a, a, bs=16, engine="jnp")
    assert s.last_call["engine"] == "jnp" and not s.last_call["degraded"]
    ref = spgemm(a, a)
    assert np.array_equal(c.indptr, ref.indptr)
    assert np.array_equal(c.indices, ref.indices)
    assert np.array_equal(c.data, ref.data)
