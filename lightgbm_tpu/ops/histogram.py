"""Histogram construction: the GBDT hot loop, TPU-style.

Re-design of the reference's histogram kernels
(/root/reference/src/io/dense_bin.hpp:99 ``ConstructHistogramInner``,
src/treelearner/cuda/cuda_histogram_constructor.cu:18): per-row (grad, hess)
scatter-add into ``[num_features, num_bins, 2]`` accumulators.

Design notes (TPU-first):
- Histogram entries are (sum_grad, sum_hess) pairs ONLY — exactly like the
  reference (``kHistEntrySize = 2 * sizeof(hist_t)``, bin.h:39). Per-bin
  data counts are *estimated* downstream from the hessian ratio
  ``cnt = RoundInt(hess * num_data / sum_hessian)``
  (feature_histogram.hpp:528,543), so no count channel is accumulated.
- The bin matrix is stored transposed ``[F, n]`` (column-major, like the
  reference's DenseBin) so one feature's bins are a contiguous vector.
- The fast path is the *nibble decomposition*: a bin index b = 16*hi + lo
  turns the histogram into HI^T @ (LO * payload) — dense batched matmuls
  that ride the MXU instead of scatter hardware (which XLA serializes on
  TPU). With the 2-channel payload an 8-feature pack is a [128, S] x
  [S, 256] matmul — both dims exact multiples of the 128-lane MXU tile.
- Precision: the default float path runs single-pass bf16-input/f32-accum
  matmuls (the MXU's native mode). The reference's GPU learner documents
  AUC parity with single-precision histograms at 255 bins
  (docs/GPU-Performance.rst:134-158); ``precision="high"|"highest"``
  (3/6-pass emulation) are available for stricter accumulation.
- Quantized int8 payloads are EXACT: int8 values are exactly
  representable in bf16, products against a {0,1} one-hot are exact, and
  f32 accumulation of a <=8192-row block is exact (|sum| <= 8192*127 <
  2^24); each block is converted to int32 before the cross-block sum, so
  the result equals true int32 accumulation at full MXU speed.
- There is no most-frequent-bin omission / ``FixHistogram`` reconstruction
  (dataset.h:760): every bin is accumulated directly, which on TPU costs
  nothing extra and removes a cross-rank reconstruction step.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import scoped

__all__ = ["build_histogram", "subtract_histogram", "hist_from_rows",
           "hist_from_rows_int", "PACK"]

PACK = 4           # features per MXU pack. The matmul computes all
                   # PACK x PACK cross-feature blocks and keeps the
                   # diagonal, so FLOPs per feature scale with PACK —
                   # while the materialized one-hot bytes per feature
                   # (s_hi + s_lo*C values) don't depend on it.
                   # PACK=4 does half the FLOPs of 8, and 2 leaves an
                   # M=16 matmul that streams the MXU poorly; the
                   # three are not measured on a local chip.
S_LO = 16          # bins per low-digit group: b = S_LO*hi + lo. With
                   # PACK=4 the 16x16 split keeps the matmul N dim at
                   # PACK*S_LO*C = 128 — exactly the MXU's output lanes
                   # — and sits at the one-hot byte optimum
                   # min(s_hi + s_lo*C) s.t. s_hi*s_lo >= num_bins.
ROW_BLOCK = 16384  # rows per accumulation block (bounds one-hot residency
                   # AND keeps int-as-bf16 block sums exact:
                   # 16384*127 = 2.1M < 2^24; sized to the compact
                   # grower's chunk so a chunk histogram is ONE block)

_PRECISIONS = {
    "default": None,
    "high": lax.Precision.HIGH,
    "highest": lax.Precision.HIGHEST,
}


def _nibble_hist_block(rows: jnp.ndarray, payload: jnp.ndarray,
                       s_hi: int, precision, int_exact: bool) -> jnp.ndarray:
    """One row-block of the nibble-decomposed MXU histogram.

    ``hist[f, b] = sum_r [bins[r,f]==b] * payload[r]`` with
    ``b = S_LO*hi + lo`` factors into
    ``sum_r HI[r, f*s_hi+hi] * LO[r, f*S_LO+lo] * payload[r]``:
    a dense [PACK*s_hi, S] x [S, PACK*S_LO*C] matmul per PACK-feature
    group — the MXU replacement for the CUDA shared-memory scatter-add
    (/root/reference/src/treelearner/cuda/cuda_histogram_constructor.cu:18).
    Cross-feature (p != q) blocks of the product are computed and
    discarded; the MXU does them for free within the 128-lane tile.

    Args:
      rows: ``[S, npacks, PACK]`` native-width (u8/u16) bin values —
        kept narrow so the materialized compare operands stay small.
      payload: ``[S, C]`` float or int8 channels (grad, hess).
    Returns:
      ``[npacks, PACK, s_hi * S_LO, C]`` partial histograms, f32 (exact
      integers when ``int_exact``).
    """
    S, npacks, P = rows.shape
    C = payload.shape[-1]
    # bf16 one-hots whenever the TPU matmul runs in single-pass mode:
    # the MXU truncates DEFAULT-precision f32 inputs to bf16 anyway,
    # and {0,1} masks commute with truncation (LOC is pay-or-zero), so
    # the result is bit-identical on TPU while the materialized
    # one-hot traffic halves. Multi-pass
    # "high"/"highest" emulation needs true f32 operands, and CPU
    # matmuls don't truncate, so both keep the payload dtype there.
    bf16_pass = int_exact or (precision is None
                              and jax.default_backend() == "tpu")
    onehot_dtype = jnp.bfloat16 if bf16_pass else payload.dtype
    if int_exact:
        precision = None
    if bf16_pass:
        payload = payload.astype(jnp.bfloat16)
    rdt = rows.dtype
    hi = rows // rdt.type(S_LO)
    lo = rows & rdt.type(S_LO - 1)
    HI = (hi[..., None] == jnp.arange(s_hi, dtype=rdt)) \
        .astype(onehot_dtype)
    LO = (lo[..., None] == jnp.arange(S_LO, dtype=rdt)) \
        .astype(onehot_dtype)
    LOC = LO[..., None] * payload[:, None, None, None, :]  # [S,np,P,sl,C]
    out = jnp.einsum(
        "snx,snyc->nxyc",
        HI.reshape(S, npacks, P * s_hi),
        LOC.reshape(S, npacks, P * S_LO, C),
        preferred_element_type=jnp.float32,
        precision=precision)
    d = jnp.diagonal(out.reshape(npacks, P, s_hi, P, S_LO, C),
                     axis1=1, axis2=3)                    # [np,hi,sl,C,P]
    return d.transpose(0, 4, 1, 2, 3).reshape(npacks, P, s_hi * S_LO, C)


def _hist_from_rows_impl(rows: jnp.ndarray, payload: jnp.ndarray,
                         num_bins: int, method: str,
                         accum_dtype, precision) -> jnp.ndarray:
    if method == "scatter":
        return _hist_scatter(rows.T, payload.astype(accum_dtype), num_bins)
    int_exact = jnp.issubdtype(accum_dtype, jnp.integer)
    if method == "pallas":
        # VMEM-resident one-hot kernel (ops/pallas_hist.py). Always
        # f32-accumulated (int8 payloads: exact int32) — the
        # hist_precision multi-pass emulation is an MXU-path knob.
        from .pallas_hist import hist_from_rows_pallas
        return hist_from_rows_pallas(rows, payload, num_bins,
                                     int_exact=int_exact)
    S, F = rows.shape
    C = payload.shape[-1]
    s_hi = -(-num_bins // S_LO)
    f_pad = (-F) % PACK
    if f_pad:
        rows = jnp.pad(rows, ((0, 0), (0, f_pad)))
    Fp = F + f_pad
    npacks = Fp // PACK
    if not jnp.issubdtype(rows.dtype, jnp.unsignedinteger):
        rows = rows.astype(jnp.uint32)
    rows = rows.reshape(S, npacks, PACK)

    def finish(block):
        return block.astype(accum_dtype) if int_exact else block

    if S <= ROW_BLOCK:
        h = finish(_nibble_hist_block(rows, payload, s_hi, precision,
                                      int_exact))
    else:
        nblk = -(-S // ROW_BLOCK)
        pad = nblk * ROW_BLOCK - S
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0), (0, 0)))
            payload = jnp.pad(payload, ((0, pad), (0, 0)))
        rows_b = rows.reshape(nblk, ROW_BLOCK, npacks, PACK)
        pay_b = payload.reshape(nblk, ROW_BLOCK, C)

        def body(acc, xs):
            r, p = xs
            blk = _nibble_hist_block(r, p, s_hi, precision, int_exact)
            return acc + finish(blk), None

        init = jnp.zeros((npacks, PACK, s_hi * S_LO, C), accum_dtype)
        h, _ = lax.scan(body, init, (rows_b, pay_b))
    h = h.reshape(Fp, s_hi * S_LO, C)
    return h[:F, :num_bins, :]


@scoped("grow/hist/build")
def hist_from_rows(rows: jnp.ndarray, payload: jnp.ndarray,
                   num_bins: int, method: str = "mxu",
                   precision: str = "default") -> jnp.ndarray:
    """Float histogram over a row-block matrix.

    Args:
      rows: ``[S, F]`` integer bin matrix (row-major).
      payload: ``[S, C]`` float per-row channels (grad, hess).
      num_bins: B.
      method: "mxu" (nibble matmul), "pallas" (VMEM-resident one-hot
        kernel, ops/pallas_hist.py) or "scatter" (CPU-friendly).
      precision: matmul pass count — "default" (1-pass bf16/f32-accum),
        "high" (3-pass), "highest" (6-pass): two and three as compiled,
        the one-hot operand's zero low half being skipped; mxu path only.
    Returns:
      ``[F, B, C]`` histograms (padding features report zeros only if the
      caller masked their payload; callers crop to the true F).
    """
    acc = jnp.promote_types(payload.dtype, jnp.float32)
    return _hist_from_rows_impl(rows, payload, num_bins, method,
                                acc, _PRECISIONS[precision])


@scoped("grow/hist/build")
def hist_from_rows_int(rows: jnp.ndarray, payload: jnp.ndarray,
                       num_bins: int, method: str = "mxu") -> jnp.ndarray:
    """Quantized histogram: int8 payload, exact int32 result
    (subtraction-safe) via bf16 MXU passes with per-block conversion."""
    return _hist_from_rows_impl(rows, payload, num_bins, method, jnp.int32,
                                None)


def _hist_scatter(bins_T: jnp.ndarray, gh: jnp.ndarray, num_bins: int,
                  unroll: int = 1) -> jnp.ndarray:
    """Scatter-add path: lax.scan over features, one scatter per feature."""

    def body(carry, bins_f):
        hist = jnp.zeros((num_bins, gh.shape[-1]), dtype=gh.dtype)
        hist = hist.at[bins_f].add(gh, mode="drop")
        return carry, hist

    _, hists = lax.scan(body, None, bins_T, unroll=unroll)
    return hists


@scoped("grow/hist/build")
def build_histogram(bins_T: jnp.ndarray,
                    grad: jnp.ndarray,
                    hess: jnp.ndarray,
                    row_weight: jnp.ndarray,
                    mask: jnp.ndarray,
                    num_bins: int,
                    method: str = "scatter",
                    precision: str = "default") -> jnp.ndarray:
    """Build per-feature histograms for the rows selected by ``mask``.

    Args:
      bins_T: ``[F, n]`` integer bin matrix (feature-major).
      grad, hess: ``[n]`` float gradients/hessians.
      row_weight: ``[n]`` sampling weight (bagging mask / GOSS
        amplification); scales the payload.
      mask: ``[n]`` bool leaf-membership mask.
      num_bins: global max number of bins B.

    Returns:
      ``[F, B, 2]`` float array of (sum_grad, sum_hess).
    """
    m = mask.astype(grad.dtype) * row_weight.astype(grad.dtype)
    gh = jnp.stack([grad * m, hess * m], axis=-1)  # [n, 2]
    if method in ("mxu", "pallas"):
        return hist_from_rows(bins_T.T, gh, num_bins, method, precision)
    return _hist_scatter(bins_T, gh, num_bins)


@scoped("grow/hist/subtract")
def subtract_histogram(parent: jnp.ndarray, child: jnp.ndarray) -> jnp.ndarray:
    """The histogram-subtraction trick: sibling = parent - child
    (serial_tree_learner.cpp:473-520)."""
    return parent - child
