"""chip_smoke.py off the chip: the explicit CPU self-test passes, the
default invocation refuses to run without a TPU, and the compile-cache
helper places the cache the way the chip tool needs."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/root")}
    env.update(extra)
    return env


def test_cpu_selftest_passes_and_says_cpu(tmp_path):
    cache = str(tmp_path / "xla_cache")
    r = subprocess.run(
        [sys.executable, SMOKE, "--cpu-selftest", "--rows", "20000"],
        env=_env(JAX_COMPILATION_CACHE_DIR=cache), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    report, verdict = r.stdout.strip().splitlines()[-2:]
    # the last line is the verdict and holds exactly these keys
    assert json.loads(verdict) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    rec = json.loads(report)
    assert rec["platform"] == "cpu" and rec["rows"] == 20000
    assert rec["compiled_entries"] == {"gbdt/fused_iter": 1}
    assert rec["recompiles_after_warmup"] == 0
    assert rec["fault_events"] == {} and rec["native_loaded"] is True
    assert rec["save_load_parity_exact"] is True
    assert rec["serve"]["replies_checked"] == 3
    assert rec["serve"]["max_abs_err"] <= 1e-6
    # a placed cache is used as placed (whether anything lands in it
    # depends on a CPU compile outlasting JAX's 1 s caching threshold)
    assert rec["compile_cache_dir"] == cache


def test_default_invocation_refuses_cpu(tmp_path):
    """No flag, no TPU: non-zero exit, the real error, no result line —
    JAX_PLATFORMS=cpu must not turn the smoke into a CPU run."""
    r = subprocess.run(
        [sys.executable, SMOKE],
        env=_env(JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "chip_smoke needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout


_PROBE = (
    "import jax; "
    "from lightgbm_tpu.utils.compile_cache import configure_compile_cache"
    "; d = configure_compile_cache(); "
    "print(d); print(jax.config.jax_compilation_cache_dir)")


def _probe(cwd, **extra):
    r = subprocess.run([sys.executable, "-c", _PROBE], cwd=cwd,
                       env=_env(PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                                **extra),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-2:]


def test_compile_cache_env_var_wins(tmp_path):
    placed = str(tmp_path / "placed")
    returned, configured = _probe(str(tmp_path),
                                  JAX_COMPILATION_CACHE_DIR=placed)
    # JAX read the variable itself; the helper named no other directory
    assert returned == placed and configured == placed


def test_compile_cache_default_is_fixed_in_checkout(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    assert _probe(str(tmp_path)) == [want, want]
    assert _probe(REPO) == [want, want]
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored
