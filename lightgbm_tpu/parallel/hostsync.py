"""Host-level collective transport with watchdog deadlines.

Every cross-host sync this package performs outside the jitted training
step is a *host* collective: small numpy vectors (step-consistency
checks, phase-skew snapshots) or byte blobs (serialized BinMappers,
binned row shards) exchanged between processes. Two transports provide
them:

``device``
    ``jax.experimental.multihost_utils`` — the payload rides the
    accelerator interconnect as a jitted allgather. The right choice on
    TPU/GPU pods, where it is by far the fastest path for large blobs.

``kv``
    The coordination-service key-value store that
    ``jax.distributed.initialize`` already stands up (plain gRPC to the
    rank-0 coordinator). Works on every backend — including CPU, whose
    XLA backend (jaxlib <= 0.4.x) refuses multiprocess computations
    outright — and gives *per-rank* visibility: each rank publishes
    under its own key, so a stalled peer is named exactly ("heard from
    ranks 0,2; rank 1 silent"), which a device allgather can never
    attribute.

``auto`` (default) picks ``device`` when the backend can actually run
multiprocess computations and ``kv`` otherwise;
``LIGHTGBM_TPU_HOSTSYNC=kv|device`` overrides.

Every operation runs under the collective watchdog
(:mod:`~lightgbm_tpu.resilience.watchdog`): a hang or transport error
becomes a ``LightGBMError`` naming the collective, the iteration, and
the last rank heard from, instead of blocking forever.
"""

from __future__ import annotations

import io
import itertools
import os
import threading
import time
from typing import List, Optional

import numpy as np

from ..resilience import watchdog

__all__ = ["host_allgather", "host_broadcast_bytes", "transport"]

#: per-process collective sequence number. SPMD processes execute the
#: identical sequence of host collectives (that contract is what
#: verify_step_consistency enforces), so the counter agrees across
#: ranks and makes every collective's key set unique within a run.
_SEQ = itertools.count()

#: payloads above this size get their kv keys deleted after a
#: completion barrier; smaller keys are deleted lazily (below) so the
#: coordinator's store stays bounded without a barrier per collective.
_KV_CLEANUP_BYTES = 1 << 16

#: this process's published small keys awaiting deletion. Safe to
#: delete once a LATER gather completes: completing gather epoch E
#: required reading every rank's epoch-E key, hence every rank had
#: already finished every epoch < E (and with it, every read of our
#: older keys). Every ``_kv_exchange`` runs on a FRESH watchdog worker
#: thread (and concurrent trainers on separate host threads share this
#: module), so mutations go through ``_pending_lock`` — copy under the
#: lock, talk to the kv store outside it (tpulint TPL008 proves this
#: on the lock-acquisition CFG).
_pending_delete: List[str] = []
_pending_lock = threading.Lock()


def _kv_client():
    from jax._src import distributed
    client = distributed.global_state.client
    if client is None:
        raise RuntimeError(
            "host collective requested before jax.distributed was "
            "initialized (call init_distributed first)")
    return client


def transport() -> str:
    """The effective transport: ``device`` or ``kv``."""
    mode = os.environ.get("LIGHTGBM_TPU_HOSTSYNC", "auto").lower()
    if mode in ("kv", "device"):
        return mode
    if mode != "auto":
        from ..utils.log import log_warning
        log_warning(f"LIGHTGBM_TPU_HOSTSYNC={mode!r} is not auto|kv|"
                    "device; using auto")
    import jax

    # CPU worlds keep the kv transport the CPU tests were written
    # against (see parallel/mesh.py make_mesh); the installed jaxlib
    # 0.9.0 could run the device transport over gloo
    return "kv" if jax.default_backend() == "cpu" else "device"


class _StalledRank(RuntimeError):
    """A peer did not publish within the deadline (kv transport). The
    watchdog classifies this as a timeout via ``is_timeout``."""

    is_timeout = True


def _deadline_ms() -> int:
    limit = watchdog.deadline_seconds()
    if limit <= 0:
        # watchdog explicitly disabled: honor it on the kv transport
        # too — block essentially forever rather than smuggling the
        # default deadline back in
        return 7 * 24 * 3600 * 1000
    return max(1000, int(limit * 1000))


def _outer_deadline() -> Optional[float]:
    """Watchdog deadline for the thread wrapping a kv collective: the
    kv gets time out at the configured deadline themselves (with exact
    per-rank attribution — "rank 1 never published"), so the outer
    thread deadline only backstops a hung gRPC client and must not
    race the inner one. None keeps guarded()'s own resolution."""
    limit = watchdog.deadline_seconds()
    if limit <= 0:
        return limit     # watchdog disabled: pass the 0 through
    return limit * 1.5 + 10.0


def _array_to_bytes(a: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, np.ascontiguousarray(a), allow_pickle=False)
    return buf.getvalue()


def _array_from_bytes(b: bytes) -> np.ndarray:
    return np.load(io.BytesIO(b), allow_pickle=False)


def _kv_exchange(name: str, payload: Optional[bytes],
                 gather: bool) -> List[Optional[bytes]]:
    """One kv collective: every rank publishes (``gather``) or only
    rank 0 does (broadcast), then every rank reads the expected keys.
    Per-rank blocking gets share one overall deadline, so the first
    silent peer is named with the ranks already heard from."""
    import jax

    client = _kv_client()
    me, nproc = jax.process_index(), jax.process_count()
    seq = next(_SEQ)
    prefix = f"lgbm_hostsync/{seq}/{name}"
    deadline_ms = _deadline_ms()
    if payload is not None:
        client.key_value_set_bytes(f"{prefix}/{me}", payload)
    readers = range(nproc) if gather else (0,)
    out: List[Optional[bytes]] = [None] * nproc
    heard: List[int] = []
    t0 = time.monotonic()
    for r in readers:
        if r == me and payload is not None:
            out[r] = payload
            heard.append(r)
            continue
        left_ms = deadline_ms - int((time.monotonic() - t0) * 1000)
        try:
            out[r] = client.blocking_key_value_get_bytes(
                f"{prefix}/{r}", max(1, left_ms))
        except Exception as e:
            if "DEADLINE_EXCEEDED" not in str(e):
                raise
            raise _StalledRank(
                f"rank {r} never published its '{name}' payload "
                f"(heard from ranks {heard or 'none'}; "
                f"{nproc} expected)") from e
        heard.append(r)
    size = max((len(b) for b in out if b is not None), default=0)
    if size > _KV_CLEANUP_BYTES:
        left_ms = deadline_ms - int((time.monotonic() - t0) * 1000)
        client.wait_at_barrier(f"{prefix}/done", max(1, left_ms))
        if payload is not None:
            client.key_value_delete(f"{prefix}/{me}")
    elif payload is not None:
        doomed: List[str] = []
        if gather:
            # completing a gather proves every rank finished all
            # earlier epochs, so our previously published keys are
            # dead — snapshot-and-clear under the lock, delete outside
            # it (kv deletes are gRPC round trips; never hold the lock
            # across them)
            with _pending_lock:
                doomed, _pending_delete[:] = list(_pending_delete), []
        for key in doomed:
            try:
                client.key_value_delete(key)
            except Exception:
                pass
        with _pending_lock:
            _pending_delete.append(f"{prefix}/{me}")
    return out


def host_allgather(arr: np.ndarray, name: str,
                   iteration: Optional[int] = None) -> np.ndarray:
    """Allgather one equal-shaped host array: returns ``[P, *shape]``.
    Watchdog-guarded; single-process returns ``arr[None]``."""
    import jax

    nproc = jax.process_count()
    arr = np.asarray(arr)
    if nproc <= 1:
        return arr[None]

    if transport() == "device":
        def _run():
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(arr))

        return watchdog.guarded(name, _run, iteration=iteration,
                                world=nproc)

    def _run():
        parts = _kv_exchange(name, _array_to_bytes(arr), gather=True)
        return np.stack([_array_from_bytes(p) for p in parts])

    return watchdog.guarded(name, _run, iteration=iteration,
                            world=nproc, deadline=_outer_deadline())


def host_broadcast_bytes(payload: Optional[bytes], name: str,
                         iteration: Optional[int] = None) -> bytes:
    """Broadcast rank 0's byte blob to every process (rank 0 passes the
    payload, others pass None). Watchdog-guarded; single-process
    returns the payload unchanged."""
    import jax

    nproc = jax.process_count()
    if nproc <= 1:
        return payload if payload is not None else b""

    if transport() == "device":
        def _run():
            from jax.experimental import multihost_utils

            # length-prefix so every process allocates the same buffer;
            # only rank 0's bytes matter (other ranks' payloads, if
            # passed, may differ in size)
            n = np.asarray([len(payload or b"")], np.int32)
            n = multihost_utils.broadcast_one_to_all(n)
            buf = np.zeros(int(n[0]), np.uint8)
            if jax.process_index() == 0:
                buf[: len(payload)] = np.frombuffer(payload, np.uint8)
            buf = multihost_utils.broadcast_one_to_all(buf)
            return bytes(buf.tobytes())

        return watchdog.guarded(name, _run, iteration=iteration,
                                world=nproc)

    def _run():
        me = jax.process_index()
        parts = _kv_exchange(
            name, payload if me == 0 else None, gather=False)
        return parts[0]

    return watchdog.guarded(name, _run, iteration=iteration,
                            world=nproc, deadline=_outer_deadline())
