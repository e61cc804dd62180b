"""The entry points' contracts with the machine they run on: the chip
smoke run refuses anything but a TPU, the gate suite's parent leaves the
device to its children, and the compile cache goes where it is told."""
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]


def _python(code: str, **env_over) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src") + os.pathsep + str(REPO))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_over)
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_gate_suite_parent_never_imports_jax():
    r = _python(
        "import sys\n"
        "sys.argv = ['run', '--gate-suite', 'no-such-gate']\n"
        "import benchmarks.run as run\n"
        "try:\n"
        "    run.main()\n"
        "except SystemExit as e:\n"
        "    assert 'no gates match' in str(e), e\n"
        "print('JAX_IMPORTED', 'jax' in sys.modules)\n")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "JAX_IMPORTED False" in r.stdout


def test_compile_cache_goes_where_the_variable_says(tmp_path):
    r = _python(
        "import jax\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print('DIR', enable_compile_cache())\n"
        "print('CFG', jax.config.jax_compilation_cache_dir)\n",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"DIR {tmp_path}" in r.stdout
    assert f"CFG {tmp_path}" in r.stdout


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout():
    r = _python(
        "import jax\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "a = enable_compile_cache()\n"
        "b = enable_compile_cache()\n"
        "print('DIRS', a, b, jax.config.jax_compilation_cache_dir)\n")
    assert r.returncode == 0, r.stderr[-2000:]
    want = REPO / ".jax_cache"
    assert f"DIRS {want} {want} {want}" in r.stdout
