"""``chip_smoke.py`` rehearsed on the CPU, and the compile-cache placement.

The script refuses to run anywhere but on a TPU, so its phases are driven
here one by one, at a tiny size, in Pallas interpret mode: the one-chip
serving and semiring phases in this process, the four-chip phase in a
subprocess with four fake host devices. The script itself must fail on the
CPU and when it stands alone, and never print its result line there.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, *, cwd=ROOT, env=None, timeout=300):
    env = dict(os.environ if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_one_chip_phases_at_tiny_size(chip_smoke, capsys):
    served = chip_smoke.serve_phase(256, 16, seed=0, band=16)
    assert served["plan_stats"]["nprod_max"] > 0
    assert served["traces"] == 1            # cold, hit and repack: one trace
    assert served["payload_bytes"] > 0
    semi = chip_smoke.semiring_phase(256, 16, seed=0, band=16)
    assert set(semi) == {"bool_or_and", "min_plus"}
    out = capsys.readouterr().out
    assert "match the host oracle bitwise" in out
    assert "windows=1" in out


def test_four_chip_phase_on_4_host_devices():
    script = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("cs", {str(SCRIPT)!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        info = cs.four_chip_phase(512, 16, seed=0, band=32)
        assert set(info) == {{"1d ring nparts=4", "2d summa grid=2"}}
        print("ALLOK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = _run(["-c", script], env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ALLOK" in out.stdout
    assert "shards on [0, 1, 2, 3]" in out.stdout


def test_script_fails_without_a_tpu():
    out = _run([str(SCRIPT)])
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_script_fails_alone(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _run([SCRIPT.name], cwd=tmp_path, env=env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


CACHE_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    print(enable_compile_cache())
    print(jax.config.jax_compilation_cache_dir)
    if {compile}:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(8)).block_until_ready()
""")


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    out = _run(["-c", CACHE_SCRIPT.format(compile=True)], env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == [str(tmp_path / "cache")] * 2
    assert any((tmp_path / "cache").iterdir())


def test_compile_cache_defaults_to_an_ignored_path_in_the_checkout():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = _run(["-c", CACHE_SCRIPT.format(compile=False)], env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == [str(ROOT / ".jax_cache")] * 2
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
