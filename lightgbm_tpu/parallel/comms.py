"""Quantized histogram collectives + the payload-adaptive mode chooser.

Data-parallel growth allreduces the full ``[F, B, 2]`` f32 histogram at
every split (MULTICHIP_r04: 2048 elems at F=64, B=16 — at an
Allstate-like F=4228, B=255 that is ~2M f32 elems, ~8.6 MB per
reduction), which dominates at pod scale and wide feature spaces. This
module provides the two answers named by ROADMAP open item 2:

1. **Block-quantized allreduce** (:func:`hist_allreduce`) in the
   EQuARX mold (arXiv:2506.17615): the histogram is flattened into
   256-element blocks, each block is quantized to int8/int16 with one
   f32 scale, and only the integer payload (plus the tiny scale
   vector) crosses the interconnect. Two wire strategies:

   - ``exchange`` (default for histogram-sized payloads): a two-phase
     reduce-scatter/all-gather built from ``lax.all_to_all`` +
     ``lax.all_gather`` whose wire dtype really is int8/int16 — each
     device receives every peer's quantized chunk, dequantizes and
     sums in f32, REquantizes its reduced chunk with fresh scales, and
     all-gathers the result. Per-device wire bytes drop from ~2x4xN
     (f32 ring allreduce) to ~2x1xN (int8) — the ~4x the EQuARX paper
     measures, visible to the dryrun payload audit because the
     collective operands ARE int8/int16.
   - ``psum`` (vmap-safe; used where the call site sits under
     ``jax.vmap``, e.g. the voting growers' elected-feature buffer):
     block amax is ``lax.pmax``-shared so every rank quantizes with
     the same scale, then the int values ride one ``lax.psum`` in an
     int32 accumulator (no overflow for any world size <= 2^16). The
     transport dtype stays int32, so this strategy models the wire
     saving rather than realizing it — acceptable for the small
     voting payloads; the dominant data-parallel path uses
     ``exchange``.

   **Determinism argument**: the reduced result every rank consumes is
   the output of ``all_gather`` (exchange) or ``psum`` (psum strategy)
   of integer payloads — bit-identical on every rank by construction
   (integer addition is associative-commutative-exact; all_gather is a
   broadcast of identical bytes). Split decisions derived from it are
   therefore replicated, exactly like the f32 psum they replace.

   **Error feedback** (the EF-SGD compressor-feedback loop): each rank
   keeps a local residual buffer ``ef`` the same shape as the
   histogram. Quantization consumes ``x + ef`` and the new residual is
   ``(x + ef) - dequant(sent)`` (plus, on the exchange path, the
   phase-2 requantization error of the chunk this rank owns). The
   per-round sent payloads then telescope:

       sum_k sent_k = sum_k x_k + ef_0 - ef_K

   so the ACCUMULATED dequantized error after any number of
   reductions is bounded by the final residual — one round's
   quantization step — instead of growing linearly with depth/trees.
   The growers thread ``ef`` through their loop carries
   (:mod:`lightgbm_tpu.ops.grow`).

2. **Payload-adaptive parallelism choice**
   (:func:`choose_parallel_mode`): the reference's tree_learner choice
   is a static user flag (docs/Parallel-Learning-Guide.rst: feature-
   parallel for small data, data-parallel for large data + few
   features, voting for both large); ``tree_learner=auto`` replaces it
   with a decision from the measured payload model — the same
   dtype-aware byte accounting ``__graft_entry__.dryrun_multichip``
   emits (:func:`payload_elems` / :func:`payload_bytes` seed both), in
   the spirit of automatic cross-replica sharding (arXiv:2004.13336).

Scalar/count psums (root tuples, exact child counts, SplitInfo
allreduce) stay f32: they are O(1)-to-O(B) bytes and feed count
thresholds where quantization buys nothing.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import scope, scoped

__all__ = [
    "BLOCK", "QMAX", "WIRE_ITEMSIZE", "hist_allreduce",
    "hist_reduce_scatter", "make_hist_psum_ef",
    "resolve_hist_comm", "payload_elems", "payload_bytes",
    "splitinfo_elems", "post_reduction_elems", "post_reduction_bytes",
    "choose_parallel_mode", "collective_payloads",
    "jaxpr_collective_payloads", "collective_summary",
]

#: quantization block size: one f32 scale per BLOCK elements (1.6%
#: overhead at int8). 256 keeps blocks lane-aligned on TPU.
BLOCK = 256

QMAX = {"int8": 127, "int16": 32767}
_WIRE_DTYPE = {"int8": jnp.int8, "int16": jnp.int16}

#: wire bytes per histogram element per hist_comm mode
WIRE_ITEMSIZE = {"f32": 4, "int16": 2, "int8": 1}

#: floor on block scales so an all-zero block quantizes to zeros
#: instead of NaNs
_TINY = 1e-30

#: auto hist_comm: quantize once the per-reduction f32 payload crosses
#: this many bytes (narrow histograms gain nothing and keep exact f32)
AUTO_QUANT_BYTES = 1 << 20

#: auto tree_learner: replicate rows (feature-parallel) only below this
#: many global rows — above it the one-time replication (and per-device
#: memory) dwarfs the histogram traffic it saves
FEATURE_MAX_ROWS = 1 << 16

#: auto tree_learner: stay data-parallel while one histogram reduction
#: is at most this many bytes; beyond it voting's O(2k*B) exchange wins
DATA_MAX_BYTES = 1 << 20


# ---------------------------------------------------------------------
# the quantized-allreduce primitive
# ---------------------------------------------------------------------

def _quantize(blocks: jnp.ndarray, qmax: int, wire_dtype):
    """Per-block symmetric quantization: ``[nblk, BLOCK] -> (q, scale)``
    with ``scale = amax / qmax`` so dequantization is ``q * scale``."""
    amax = jnp.max(jnp.abs(blocks), axis=1)
    scale = jnp.maximum(amax, _TINY) / qmax
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -qmax, qmax)
    return q.astype(wire_dtype), scale


def _pack_scales(q, scale, wire_dtype):
    """Append each block's f32 scale, bitcast into wire-dtype lanes, to
    its int payload: ``[nblk, BLOCK] + [nblk] -> [nblk, BLOCK + s]``.
    One homogeneous integer buffer then rides ONE collective — scales
    never travel as a separate (concurrently-rendezvousing) f32 op,
    and the wire really is pure int8/int16."""
    s = 4 // jnp.dtype(wire_dtype).itemsize          # lanes per f32
    sw = lax.bitcast_convert_type(scale[:, None], wire_dtype)
    return jnp.concatenate([q, sw.reshape(q.shape[0], s)], axis=1)


def _unpack_scales(packed, wire_dtype):
    """Inverse of :func:`_pack_scales` -> (q [.., BLOCK], scale [..])."""
    s = 4 // jnp.dtype(wire_dtype).itemsize
    q = packed[..., :BLOCK]
    scale = lax.bitcast_convert_type(
        packed[..., BLOCK:].reshape(packed.shape[:-1] + (1, s)),
        jnp.float32)
    return q, scale.reshape(packed.shape[:-1])


def _allreduce_exchange(blocks, scale_q, axis_name, qmax, wire_dtype,
                        D, dtype):
    """Two-phase quantized allreduce of pre-quantized blocks.

    Phase 1 (reduce-scatter shape): ``all_to_all`` routes chunk ``i``
    of every rank's int payload (scales packed into the same integer
    buffer) to rank ``i``, which dequantizes and sums in f32. Phase 2:
    the owner requantizes its reduced chunk with fresh scales and
    ``all_gather`` broadcasts the packed int result. Exactly TWO
    collectives per reduction, each consuming the previous one's
    output — the strict data dependence keeps every rank's collective
    sequence in lockstep (jaxlib 0.4.37's in-process CPU rendezvous
    is racy when independent collectives are in flight together).
    Returns ``(reduced [nblk, BLOCK] f32, phase2_err [cb*BLOCK] f32)``
    — the requantization error this rank introduced on its owned
    chunk (for error feedback)."""
    nblk = blocks.shape[0]
    cb = nblk // D                                   # blocks per chunk
    pk = _pack_scales(blocks, scale_q, wire_dtype)   # [nblk, BLOCK+s]
    px = lax.all_to_all(pk.reshape(D, cb, pk.shape[1]), axis_name,
                        split_axis=0, concat_axis=0)  # [D, cb, BLOCK+s]
    qx, sx = _unpack_scales(px, wire_dtype)
    red = jnp.sum(qx.astype(dtype) * sx[..., None], axis=0)
    q2, scale2 = _quantize(red, qmax, wire_dtype)
    deq2 = q2.astype(dtype) * scale2[:, None]            # [cb, BLOCK]
    err2 = (red - deq2).reshape(-1)
    pk2 = _pack_scales(q2, scale2, wire_dtype)
    pg = lax.all_gather(pk2, axis_name, axis=0)      # [D, cb, BLOCK+s]
    qg, sg = _unpack_scales(pg, wire_dtype)
    out = qg.reshape(nblk, BLOCK).astype(dtype) \
        * sg.reshape(nblk)[:, None]
    return out, err2


def _allreduce_shared_psum(blocks, axis_name, qmax, wire_dtype, dtype):
    """Shared-scale quantized allreduce: pmax the block amax so every
    rank quantizes with the SAME scale, then ``sum_r q_r * scale =
    scale * psum(q_r)`` holds exactly. int32 transport (headroom for
    any world <= 2^16 at int16); batches under jax.vmap, unlike
    all_to_all. Returns (reduced [nblk, BLOCK], sent-dequant
    [nblk, BLOCK]) — the latter is this rank's contribution as the
    wire saw it (for error feedback)."""
    amax = lax.pmax(jnp.max(jnp.abs(blocks), axis=-1), axis_name)
    scale = jnp.maximum(amax, _TINY) / qmax
    q = jnp.clip(jnp.round(blocks / scale[..., None]), -qmax, qmax)
    q = q.astype(wire_dtype)
    total = lax.psum(q.astype(jnp.int32), axis_name)
    out = total.astype(dtype) * scale[..., None]
    sent = q.astype(dtype) * scale[..., None]
    return out, sent


#: the histogram reductions of the program being traced, one record a
#: reduction SITE: ``(per, wire, bytes)``. ``per`` says how often the
#: site runs in one execution of the program — ``"tree"`` (the root's),
#: ``"split"`` (once a split: the smaller child's) or ``"level"`` (the
#: level grower's scatter batch) — and is set by the grower around its
#: call (:func:`reduction_site`); ``bytes`` is the local operand's
#: size, what one rank hands the collective. Filled at TRACE time only:
#: the engine clears it before the first call of its grow program and
#: keeps a copy after (models/gbdt.py ``_reduction_sites``), then
#: multiplies by what executed into ``hist_reductions`` /
#: ``hist_wire_bytes{wire}``.
traced_reductions: list = []
_site_per = ["split"]


@contextlib.contextmanager
def reduction_site(per: str):
    """``with reduction_site("tree"):`` around a grower's reduction
    that does not run once a split."""
    _site_per.append(per)
    try:
        yield
    finally:
        _site_per.pop()


def _note_reduction(x, wire: str) -> None:
    traced_reductions.append(
        (_site_per[-1], wire, x.size * x.dtype.itemsize))


@scoped("grow/hist/allreduce")
def hist_allreduce(x: jnp.ndarray, axis_name, mode: str = "f32",
                   error_feedback: Optional[jnp.ndarray] = None,
                   strategy: str = "auto"):
    """Allreduce a histogram across ``axis_name`` under ``mode``.

    ``mode="f32"`` (or a non-floating ``x``, e.g. the exact int32
    histograms of quantized-gradient training) is a plain
    ``lax.psum``. ``"int16"``/``"int8"`` run the block-quantized
    reduction described in the module docstring. With
    ``error_feedback`` (a buffer of ``x``'s shape) the return is
    ``(reduced, new_error_feedback)``; without it, just ``reduced``.

    ``strategy="auto"`` resolves (at trace time) to ``"exchange"`` —
    the int-wire all_to_all/all_gather pair — on TPU, and to the
    shared-scale ``"psum"`` transport on CPU: jaxlib 0.4.37's
    in-process CPU collective rendezvous stalls 5s+ (and, before the
    scales were packed into the int payload, deadlocked outright)
    when all_to_all/all_gather pairs cycle in a tight loop, while the
    pmax->psum chain is the pattern every existing multi-device test
    exercises. ``LIGHTGBM_TPU_COMM_EXCHANGE=1`` forces the exchange
    path for wire-level audits on CPU.

    The result is replicated — bit-identical on every rank — for every
    mode/strategy (see the determinism argument above), so split
    decisions computed from it never diverge.
    """
    has_ef = error_feedback is not None

    def ret(y, ef):
        return (y, ef) if has_ef else y

    if axis_name is None:
        return ret(x, error_feedback)
    quantized = mode in ("int8", "int16") \
        and jnp.issubdtype(x.dtype, jnp.floating)
    _note_reduction(x, mode if quantized else "f32")
    if not quantized:
        return ret(lax.psum(x, axis_name), error_feedback)
    D = lax.axis_size(axis_name)
    if D == 1:
        return ret(x, error_feedback)
    if strategy == "auto":
        import os
        if jax.default_backend() == "tpu" \
                or os.environ.get("LIGHTGBM_TPU_COMM_EXCHANGE") == "1":
            strategy = "exchange"
        else:
            strategy = "psum"

    qmax = QMAX[mode]
    wire_dtype = _WIRE_DTYPE[mode]
    dtype = x.dtype
    shape = x.shape
    n = x.size
    xe = x if not has_ef else x + error_feedback

    if strategy == "psum":
        pad = (-n) % BLOCK
        blocks = jnp.pad(xe.reshape(-1), (0, pad)) \
            .reshape((n + pad) // BLOCK, BLOCK)
        out_b, sent_b = _allreduce_shared_psum(blocks, axis_name, qmax,
                                               wire_dtype, dtype)
        y = out_b.reshape(-1)[:n].reshape(shape)
        new_ef = None
        if has_ef:
            new_ef = xe - sent_b.reshape(-1)[:n].reshape(shape)
        return ret(y, new_ef)

    # exchange strategy: flatten, pad to a D*BLOCK multiple
    step = D * BLOCK
    np_ = -(-n // step) * step
    flat = jnp.pad(xe.reshape(-1), (0, np_ - n))
    nblk = np_ // BLOCK
    blocks = flat.reshape(nblk, BLOCK)
    q, scale = _quantize(blocks, qmax, wire_dtype)
    out_b, err2 = _allreduce_exchange(q, scale, axis_name, qmax,
                                      wire_dtype, D, dtype)
    y = out_b.reshape(-1)[:n].reshape(shape)
    if not has_ef:
        return y
    sent = q.astype(dtype) * scale[:, None]
    ef_flat = (blocks - sent).reshape(-1)                # [np_]
    # fold the phase-2 requantization error of the chunk THIS rank
    # owns into its residual (the owner introduced it)
    cbe = np_ // D
    off = lax.axis_index(axis_name) * cbe
    cur = lax.dynamic_slice(ef_flat, (off,), (cbe,))
    ef_flat = lax.dynamic_update_slice(ef_flat, cur + err2, (off,))
    new_ef = ef_flat[:n].reshape(shape)
    return y, new_ef


def make_hist_psum_ef(axis_name, hist_comm: str, quantize: bool = True):
    """The one wire-mode decision every grower shares: resolve the
    histogram wire format and build the EF-threaded reduction closure
    whose residual the growers carry through their loops
    (ops/grow.py). ``quantize=False`` pins the wire to exact f32
    regardless of ``hist_comm`` — the compact grower passes it for
    feature/voting-parallel (no full-histogram reduction) and
    quantized-gradient training (exact int32 histograms already).

    Returns ``(qm, use_ef, hist_psum_ef)``: the resolved wire mode,
    whether an error-feedback buffer must be allocated/carried, and
    ``hist_psum_ef(x, ef) -> (reduced, new_ef)`` — identity on a
    single device, exact ``lax.psum`` (``ef`` untouched) at f32 wire,
    the quantized :func:`hist_allreduce` otherwise."""
    qm = hist_comm if (axis_name is not None and quantize
                       and hist_comm in ("int8", "int16")) else "f32"
    use_ef = qm != "f32"

    def hist_psum_ef(x, ef):
        if axis_name is None:
            return x, ef
        if not use_ef:
            _note_reduction(x, "f32")
            with scope("grow/hist/allreduce"):
                return lax.psum(x, axis_name), ef
        return hist_allreduce(x, axis_name, qm, ef)

    return qm, use_ef, hist_psum_ef


# ---------------------------------------------------------------------
# the reduce-scatter primitive (sharded split search)
# ---------------------------------------------------------------------

@scoped("grow/hist/allreduce")
def hist_reduce_scatter(x: jnp.ndarray, axis_name, mode: str = "f32",
                        error_feedback: Optional[jnp.ndarray] = None,
                        scatter_axis: int = 0):
    """Reduce ``x`` across ``axis_name`` and return only THIS device's
    chunk of ``scatter_axis`` — the reference data-parallel learner's
    ``ReduceScatter`` (network.h) as a first-class wire primitive for
    ``split_search="sharded"``: each device then searches its owned
    ``F/D`` feature chunk instead of the full gathered histogram, and
    only the tiny winning SplitInfo records travel afterwards.

    ``x.shape[scatter_axis]`` must be ``D * chunk``.

    - ``mode="f32"`` (and any non-floating ``x``, e.g. exact int32
      quantized-gradient histograms): ``lax.psum_scatter`` — its chunk
      is bit-identical to the matching slice of ``lax.psum`` (on CPU by
      construction of the ordered reduction; on TPU the ring allreduce
      IS reduce-scatter + all-gather), which is what makes
      sharded-search split decisions byte-identical to the gathered
      path's.
    - ``"int8"``/``"int16"``: the int-wire exchange's phase 1
      (all_to_all of per-block-quantized payloads, scales packed into
      the same integer buffer) followed by the owner REQUANTIZING its
      reduced chunk and consuming the dequantized result — the same
      bytes the gathered exchange's phase-2 all_gather would have
      broadcast, minus the broadcast. Blocks are laid out per device
      chunk (each chunk padded to a BLOCK multiple independently), so
      chunk ownership aligns with ``scatter_axis`` slices exactly.

    With ``error_feedback`` (full ``x`` shape) the return is
    ``(chunk, new_error_feedback)`` — the residual covers the whole
    local histogram plus this rank's phase-2 requantization error on
    its owned chunk, telescoping like :func:`hist_allreduce`'s.
    Replication: every device's chunk is a pure function of the
    globally-reduced histogram, and downstream SplitInfo combines are
    allreduces — so split decisions stay identical on every rank.
    """
    has_ef = error_feedback is not None

    def ret(y, ef):
        return (y, ef) if has_ef else y

    if axis_name is None:
        return ret(x, error_feedback)
    quantized = mode in ("int8", "int16") \
        and jnp.issubdtype(x.dtype, jnp.floating)
    _note_reduction(x, mode if quantized else "f32")
    if not quantized:
        chunk = lax.psum_scatter(x, axis_name,
                                 scatter_dimension=scatter_axis,
                                 tiled=True)
        return ret(chunk, error_feedback)
    D = lax.axis_size(axis_name)
    if D == 1:
        return ret(x, error_feedback)

    qmax = QMAX[mode]
    wire_dtype = _WIRE_DTYPE[mode]
    dtype = x.dtype
    xe = x if not has_ef else x + error_feedback
    xm = jnp.moveaxis(xe, scatter_axis, 0)
    cs = xm.shape[0] // D                    # chunk rows
    per = xm.size // D                       # elements per chunk
    flat = xm.reshape(D, per)
    pad = (-per) % BLOCK
    fl = jnp.pad(flat, ((0, 0), (0, pad)))   # [D, per + pad]
    cb = (per + pad) // BLOCK
    blocks = fl.reshape(D * cb, BLOCK)
    q, scale = _quantize(blocks, qmax, wire_dtype)
    pk = _pack_scales(q, scale, wire_dtype)  # [D*cb, BLOCK+s]
    px = lax.all_to_all(pk.reshape(D, cb, pk.shape[1]), axis_name,
                        split_axis=0, concat_axis=0)  # [D, cb, BLOCK+s]
    qx, sx = _unpack_scales(px, wire_dtype)
    red = jnp.sum(qx.astype(dtype) * sx[..., None], axis=0)  # [cb, BLOCK]
    q2, scale2 = _quantize(red, qmax, wire_dtype)
    deq2 = q2.astype(dtype) * scale2[:, None]
    chunk = deq2.reshape(-1)[:per].reshape((cs,) + xm.shape[1:])
    chunk = jnp.moveaxis(chunk, 0, scatter_axis)
    if not has_ef:
        return chunk
    sent = q.astype(dtype) * scale[:, None]          # [D*cb, BLOCK]
    ef_full = (blocks - sent).reshape(D, per + pad)[:, :per]
    err2 = (red - deq2).reshape(-1)[:per]            # own-chunk requant
    own = lax.axis_index(axis_name)
    cur = lax.dynamic_index_in_dim(ef_full, own, keepdims=False)
    ef_full = lax.dynamic_update_index_in_dim(ef_full, cur + err2, own,
                                              axis=0)
    new_ef = jnp.moveaxis(ef_full.reshape(xm.shape), 0, scatter_axis)
    return chunk, new_ef


# ---------------------------------------------------------------------
# payload model (seeds dryrun_multichip's accounting AND the auto
# tree_learner choice)
# ---------------------------------------------------------------------

def payload_elems(mode: str, F: int, B: int, top_k: int = 20) -> int:
    """Largest per-reduction collective payload (ELEMENTS) of one
    split search under parallelism ``mode`` — the quantity
    ``dryrun_multichip`` measures in the lowered StableHLO
    (MULTICHIP_r04 at F=64, B=16, k=3: data 2048 >> voting 384 >>
    feature 32).

    - ``data``: the full ``[F, B, 2]`` histogram psum.
    - ``voting``: the elected ``[k2, B, 2]`` buffer, x2 because both
      children's searches fuse into one vmapped collective
      (CopyLocalHistogram, parallel_tree_learner.h:153-161).
    - ``feature``: the SplitInfo allreduce only — scalars plus one
      ``[B]`` categorical mask, bounded by ``2B``.
    """
    if mode == "data":
        return F * B * 2
    if mode == "voting":
        return 2 * min(2 * top_k, F) * B * 2
    if mode == "feature":
        return 2 * B
    raise ValueError(f"unknown parallel mode: {mode}")


def payload_bytes(mode: str, F: int, B: int, hist_comm: str = "f32",
                  top_k: int = 20) -> int:
    """Dtype-aware wire BYTES of :func:`payload_elems`, including the
    per-block f32 scale overhead of the quantized modes. Histogram
    payloads (data/voting) scale with ``hist_comm``; the feature-mode
    SplitInfo stays f32 by design."""
    elems = payload_elems(mode, F, B, top_k)
    if mode == "feature" or hist_comm not in ("int8", "int16"):
        return elems * 4
    scales = -(-elems // BLOCK) * 4
    return elems * WIRE_ITEMSIZE[hist_comm] + scales


def splitinfo_elems(B: int) -> int:
    """Elements of ONE SplitInfo allreduce record: the scalar fields
    plus the ``[B]`` categorical membership mask — the same ``2B``
    bound the feature-parallel payload model uses."""
    return 2 * B


def post_reduction_elems(mode: str, F: int, B: int, D: int = 1,
                         split_search: str = "gathered",
                         top_k: int = 20) -> int:
    """POST-reduction split-search payload per device (ELEMENTS): what
    each device RECEIVES after the reduce phase, per split search.

    - ``gathered`` data-parallel: the full ``[F, B, 2]`` reduced
      histogram is broadcast back to every device (the all-gather arm
      of the ring allreduce).
    - ``sharded`` data-parallel (``split_search="sharded"``): each
      device receives only its owned ``ceil(F/D)`` feature chunk from
      the reduce-scatter, plus the ``O(D)`` per-device best-SplitInfo
      records of the combine.
    - other modes: unchanged from :func:`payload_elems` (voting's
      elected buffer / feature's SplitInfo are already small).
    """
    if mode == "data" and split_search == "sharded" and D > 1:
        return -(-F // D) * B * 2 + D * splitinfo_elems(B)
    return payload_elems(mode, F, B, top_k)


def post_reduction_bytes(mode: str, F: int, B: int, D: int = 1,
                         split_search: str = "gathered",
                         hist_comm: str = "f32", top_k: int = 20) -> int:
    """Dtype-aware wire BYTES of :func:`post_reduction_elems`. The
    histogram part scales with ``hist_comm`` (chunk or full broadcast);
    SplitInfo records stay f32 by design."""
    if mode == "data" and split_search == "sharded" and D > 1:
        chunk = -(-F // D) * B * 2
        if hist_comm in ("int8", "int16"):
            scales = -(-chunk // BLOCK) * 4
            hist_b = chunk * WIRE_ITEMSIZE[hist_comm] + scales
        else:
            hist_b = chunk * 4
        return hist_b + D * splitinfo_elems(B) * 4
    return payload_bytes(mode, F, B, hist_comm, top_k)


def resolve_hist_comm(hist_comm: str, F: int, B: int,
                      parallel_mode: str = "data",
                      top_k: int = 20) -> str:
    """Concrete wire mode for ``hist_comm="auto"``: quantize to int16
    once one f32 histogram reduction OF THE ACTIVE PARALLELISM MODE
    crosses ``AUTO_QUANT_BYTES`` (voting's elected buffer is far
    smaller than the full data-parallel histogram, so auto under
    voting stays exact until the elected payload itself is heavy;
    int16 keeps eval parity within tolerance — int8 stays opt-in
    until the on-chip quant_bench comms arm records its verdict);
    narrow histograms keep exact f32."""
    if hist_comm != "auto":
        return hist_comm
    wire_f32 = payload_bytes(parallel_mode, F, B, "f32", top_k)
    return "int16" if wire_f32 >= AUTO_QUANT_BYTES else "f32"


def choose_parallel_mode(F: int, B: int, rows: int, world: int,
                         hist_comm: str = "f32",
                         top_k: int = 20) -> str:
    """Pick data|voting|feature parallelism from the payload model —
    the ``tree_learner=auto`` decision.

    The reference's Parallel-Learning-Guide decision table (small data
    -> feature; large data + narrow -> data; large + wide -> voting),
    re-derived from measured bytes instead of adjectives:

    - ``feature`` when the dataset is small enough to replicate
      (``rows <= FEATURE_MAX_ROWS``): per-split traffic collapses to
      the SplitInfo allreduce and each device still does 1/D of the
      histogram work over its feature shard.
    - ``data`` while one histogram reduction, at the chosen wire
      dtype, stays under ``DATA_MAX_BYTES`` (or when voting cannot
      elect fewer features than exist, ``F <= 2*top_k``): exact
      reductions, no voting approximation.
    - ``voting`` otherwise: the exchange drops to the elected
      ``O(2k*B)`` buffer regardless of F (PV-Tree).
    """
    if world <= 1:
        return "data"
    if rows <= FEATURE_MAX_ROWS:
        return "feature"
    if F <= 2 * top_k:
        return "data"
    wire = resolve_hist_comm(hist_comm, F, B)
    if payload_bytes("data", F, B, wire, top_k) <= DATA_MAX_BYTES:
        return "data"
    return "voting"


# ---------------------------------------------------------------------
# jaxpr payload audit (dryrun_multichip + tests)
# ---------------------------------------------------------------------

#: collective primitives whose operands count as wire payload
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "psum_invariant",
    # jax>=0.8 varying-manual-axes (check_vma=True) names
    "psum2",
})


def collective_payloads(fn, *args):
    """Trace ``fn(*args)`` and return one record per collective operand
    in the jaxpr: ``{"prim", "elems", "itemsize", "bytes"}`` —
    dtype-aware, so a quantized allreduce's int8 operands report 1/4
    the bytes of the f32 psum they replace."""
    return jaxpr_collective_payloads(jax.make_jaxpr(fn)(*args))


def jaxpr_collective_payloads(closed):
    """:func:`collective_payloads` over an already-traced ClosedJaxpr
    (so callers needing the jaxpr for other audits trace once)."""
    records = []

    def _sub(val):
        import jax.extend.core as jcore
        if isinstance(val, jcore.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, jcore.Jaxpr):
            yield val
        elif isinstance(val, (tuple, list)):
            for v in val:
                yield from _sub(v)

    eqn_seq = [0]

    def _walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in COLLECTIVE_PRIMS:
                eqn_seq[0] += 1
                # output side too: a psum RETURNS the full reduced
                # operand where a psum_scatter returns 1/D of it — the
                # out bytes are the post-reduction payload the sharded
                # split search exists to shrink
                out_elems = out_bytes = 0
                for v in eqn.outvars:
                    aval = getattr(v, "aval", None)
                    if aval is None or not hasattr(aval, "size"):
                        continue
                    out_elems += int(aval.size)
                    out_bytes += int(aval.size) \
                        * int(jnp.dtype(aval.dtype).itemsize)
                for v in eqn.invars:
                    aval = getattr(v, "aval", None)
                    if aval is None or not hasattr(aval, "size"):
                        continue
                    itemsize = jnp.dtype(aval.dtype).itemsize
                    records.append({
                        "prim": eqn.primitive.name,
                        "eqn": eqn_seq[0],
                        "elems": int(aval.size),
                        "itemsize": int(itemsize),
                        "bytes": int(aval.size) * int(itemsize),
                        "elems_out": out_elems,
                        "bytes_out": out_bytes,
                    })
            for val in eqn.params.values():
                for sub in _sub(val):
                    _walk(sub)

    _walk(closed.jaxpr)
    return records


def collective_summary(closed) -> dict:
    """Budget view of a traced program's collectives — the numbers
    ``lint --ir`` (TPL012, analysis/ircheck.py) diffs against the
    committed ``tools/ir_budgets.json``:

    - ``wire_bytes``: total operand bytes entering collectives (the
      payload the int8/int16 hist wire shrinks 4x/2x),
    - ``post_reduction_bytes``: total bytes the collectives RETURN
      (the payload ``split_search=sharded``'s psum_scatter cuts ~D x
      vs a full psum),
    - ``n_collectives`` / ``prims``: the collective census.

    Out-bytes are counted once per collective *equation* (a
    multi-operand psum contributes one output, not one per operand)."""
    records = jaxpr_collective_payloads(closed)
    out_by_eqn = {}
    for r in records:
        out_by_eqn[r["eqn"]] = r["bytes_out"]
    return {
        "n_collectives": len(out_by_eqn),
        "prims": sorted({r["prim"] for r in records}),
        "wire_bytes": sum(r["bytes"] for r in records),
        "post_reduction_bytes": sum(out_by_eqn.values()),
    }
