"""``chip_smoke.py`` rehearsed on the CPU, and its refusals.

The smoke run's phases (load, checked mixed traffic with a compaction under
load, both kernels against the jnp path) run here at a tiny size with the
kernels in interpret mode; on the chip the same functions run at 200M keys.
The script itself must refuse to run without a TPU, and when it is not next
to the repository.  The compile-cache rule every entry point shares is
checked in fresh processes (it sets process-wide JAX config).
"""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_phases_on_cpu_tiny():
    import jax
    ok = chip_smoke.smoke(
        jax.devices(), chips=1, keys=100_000, seed=0, burst_writes=2048,
        gets_per_step=256, kernel_sizes=(8192, 32768),
        vmem_budget=256 * 1024, merge_shapes=((1, 512, 64), (4, 256, 32)),
        interpret=True)
    assert ok


def test_reference_scan_merges_writes():
    import numpy as np
    keys = np.array([10, 20, 30, 40], dtype=np.uint64)
    ref = chip_smoke.Reference(keys, keys + np.uint64(1))
    assert ref.insert(25, 7) and ref.insert(20, 9)
    assert ref.delete(30) and not ref.delete(31)
    assert ref.scan(15, 3) == [(20, 9), (25, 7), (40, 41)]
    assert ref.scan(0, 10) == [(10, 11), (20, 9), (25, 7), (40, 41)]
    assert ref.get(30) is None and ref.get(40) == 41


def _run(args, cwd, env=None):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run([str(ROOT / "chip_smoke.py")], ROOT, env)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"ok"' not in p.stdout


def test_refuses_alone(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    p = _run(["chip_smoke.py"], tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


_CACHE_PROBE = """
import sys
sys.path.insert(0, sys.argv[1])
import jax
from repro.compile_cache import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(enable_compile_cache(sys.argv[2]))
jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7)).block_until_ready()
"""


@pytest.mark.parametrize("with_env", [False, True])
def test_compile_cache_rule(tmp_path, with_env):
    root = tmp_path / "checkout"
    root.mkdir()
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    want = root / ".jax_cache"
    if with_env:
        want = tmp_path / "elsewhere"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    p = _run(["-c", _CACHE_PROBE, str(ROOT / "src"), str(root)], tmp_path,
             env)
    assert p.returncode == 0, p.stderr
    assert pathlib.Path(p.stdout.strip()) == want
    assert any(want.iterdir())
    if with_env:
        assert not (root / ".jax_cache").exists()
