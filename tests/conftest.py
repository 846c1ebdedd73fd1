"""Test configuration: force an 8-virtual-device CPU mesh.

Mirrors the reference's localhost-cluster test pattern
(tests/distributed/_test_distributed.py): multi-node is simulated on one
host — here via XLA's host-platform device partitioning instead of
loopback TCP sockets.
"""

import os

# Force CPU: the tests are written for the 8-virtual-device CPU mesh
# (exact parity oracles, Pallas in interpret mode). On a machine with a
# chip JAX defaults to the TPU, so the platform is pinned both in the
# environment (worker subprocesses inherit it) and in the config after
# importing jax (backends are not initialized yet at conftest-import
# time, so this takes effect).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _isolate_process_fault_log():
    """Tier-1 order independence: the PROCESS-LEVEL fault-event log
    (resilience.faults.FAULT_EVENTS) is drained by whichever telemetry
    recorder runs next, so a test that provokes watchdog timeouts /
    injected faults without attaching a recorder (the
    test_distributed_resilience in-process chaos tests) used to leak
    its events into an unrelated later test's JSONL stream —
    test_jsonl_schema_one_valid_event_per_iteration counted 15 lines
    for 5 iterations whenever the distributed module ran first.
    Snapshot-and-clear after every test so each starts with an empty
    process log; tests that assert on these events consume them
    inside the test body."""
    yield
    from lightgbm_tpu.resilience.faults import FAULT_EVENTS, drain_events
    if FAULT_EVENTS:
        drain_events(FAULT_EVENTS)
    # same contract for the process-level XLA compile-event queue
    # (obs/cost.py): a test that compiles jitted entry points without
    # draining would otherwise leak {"event": "compile"} lines into an
    # unrelated later test's JSONL stream
    from lightgbm_tpu.obs.cost import drain_compile_events
    drain_compile_events()
    # and for the process-level span buffer + current trace context
    # (obs/trace.py): spans recorded without an attached recorder must
    # not leak into a later test's stream, and a test that calls
    # set_current_trace must not re-parent spans of the next test
    from lightgbm_tpu.obs.trace import drain_span_events, set_current_trace
    drain_span_events()
    set_current_trace(None)


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(42)


def make_synthetic_binary(n=2000, f=10, seed=7):
    """Linearly-separable-ish binary task with noise."""
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    coef = rs.randn(f)
    logits = X @ coef + 0.5 * rs.randn(n)
    y = (logits > 0).astype(np.float64)
    return X, y


def make_synthetic_regression(n=2000, f=10, seed=7):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    coef = rs.randn(f)
    y = X @ coef + 0.1 * rs.randn(n)
    return X, y
