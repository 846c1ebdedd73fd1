"""Shared scaffolding for multi-process (subprocess-spawning) tests.

One home for the launch/cleanup idioms `tests/test_multiprocess.py`
introduced — free-port pick, session-group SIGKILL, drain-with-partial-
output — so the distributed chaos tests (test_distributed_resilience.py)
reuse them instead of re-growing copies.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Dict, Optional, Sequence

import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(TESTS_DIR)

# the free-port / group-SIGKILL primitives live in the (jax-free)
# elastic supervisor — one implementation, reused here
from lightgbm_tpu.resilience.elastic import (  # noqa: E402
    _free_port as free_port, _kill_group as kill_group)


def drain_all(procs: Sequence[subprocess.Popen], reason: str) -> None:
    """Kill every worker group and fail with their partial output —
    a hung collective must not leak orphan workers into the tier-1
    budget, and the partial logs are the only diagnostic there is."""
    for q in procs:
        kill_group(q)
    partials = []
    for rank, q in enumerate(procs):
        try:
            out, _ = q.communicate(timeout=30)
        except Exception:
            out = b""
        partials.append(f"--- rank {rank} partial output "
                        f"(returncode {q.returncode}) ---\n"
                        f"{(out or b'').decode(errors='replace')}")
    pytest.fail(reason + "; killed worker process groups.\n"
                + "\n".join(partials))


def worker_base_env(extra: Optional[Dict[str, str]] = None
                    ) -> Dict[str, str]:
    """Environment for a spawned worker: the test runner's env minus
    the single-process JAX platform pins (workers set their own), with
    the repo importable."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS",
                        "LIGHTGBM_TPU_FAULT_INJECT",
                        "LIGHTGBM_TPU_CHECKPOINT",
                        "LIGHTGBM_TPU_TELEMETRY")}
    env["PYTHONPATH"] = REPO_DIR
    if extra:
        env.update(extra)
    return env


def spawn_worker(args: Sequence[str], env: Dict[str, str],
                 **popen_kwargs) -> subprocess.Popen:
    """Start one python worker in its own session with captured
    output."""
    return subprocess.Popen(
        [sys.executable, *args], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        start_new_session=True, **popen_kwargs)


