"""Hand-tiled Pallas TPU histogram kernel (``hist_method="pallas"``).

The MXU nibble path (histogram.py) materializes its HI/LO one-hot
operands through HBM. This kernel builds the one-hot *inside* the
kernel body, so it only ever exists in VMEM:

- **Layout**: rows ride the lane dimension everywhere. The wrapper
  hands the kernel the bin matrix feature-major (``[F, S]``, its
  narrow integer dtype kept) and the payload channel-major
  (``[C, S]``), so every block's last dimension is a row tile (a
  multiple of 128 lanes) and its second-to-last is either a full array
  dimension or the 8-feature pack — the block shapes Mosaic accepts.
- **Grid** = ``(feature_packs, row_tiles)``. The row-tile dimension is
  innermost, so the ``[FPACK, C, BP]`` output block stays VMEM-resident
  across the whole row sweep of one feature pack (initialized at tile
  0, accumulated in f32 thereafter) while Pallas double-buffers the
  ``[FPACK, ROW_TILE]`` bin block and the ``[C, ROW_TILE]`` payload
  block through VMEM — the bin matrix streams HBM -> VMEM exactly once
  and nothing histogram-shaped goes back until the final result.
- **Compute**: per feature of the pack, the transposed one-hot
  ``[BP, ROW_TILE]`` (one sublane-broadcast compare of the feature's
  bin row against a bin iota) is contracted with the ``[C, ROW_TILE]``
  payload over the row (lane) dimension of both — the ``q @ k^T`` form
  of ``dot_general`` — giving a lane-dense ``[C, BP]`` partial. No
  in-kernel reshape, no cross-feature garbage.
- **Tiling**: B pads up to a 128-lane multiple; ROW_TILE is the largest
  power of two <= 1024 keeping one feature's f32 one-hot within ~4 MiB
  of VMEM (1024 rows up to B=1024, 128 rows at B=8192).
- **Exactness**: float payloads accumulate in f32 (on TPU the MXU's
  default single-pass mode reads the f32 one-hot/payload as bf16 — the
  same numerics class as the mxu path's documented default). int8
  quantized payloads are EXACT int32: each <=131072-row super-block's
  f32 sums are exact integers (131072 * 127 < 2^24) and blocks are
  converted to int32 before the cross-block sum, mirroring the mxu
  path's per-ROW_BLOCK conversion.

CPU correctness (tier-1) runs the SAME kernel under
``pallas_call(..., interpret=True)``; parity with the mxu and scatter
paths is asserted by tests/test_pallas_hist.py. On a TPU the kernel is
always compiled (``interpret=True`` there is an error);
``python chip_smoke.py --kernels`` compiles it on the chip and checks
it against the scatter histogram. Its speed against the mxu path is
not measured: ``auto`` keeps the mxu path and pallas is opt-in until a
benchmark cell decides (ROADMAP D2). docs/PALLAS.md records the tiling
rationale.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pallas_available", "hist_from_rows_pallas", "FPACK",
           "INT_BLOCK"]

FPACK = 8        # feature rows per grid cell: the sublane extent of
                 # the [FPACK, ROW_TILE] bin block
INT_BLOCK = 131072   # rows per int-exact super-block: 131072 * 127
                     # = 1.66e7 < 2^24, so every f32 partial sum of an
                     # int8 payload is an exact integer
_ONEHOT_VMEM = 4 * 2 ** 20   # one-hot block VMEM budget (bytes)

_pallas_mod = None
_pallas_checked = False


def pallas_available() -> bool:
    """Whether the Pallas kernel can be built in this environment.

    True when ``jax.experimental.pallas`` imports (the kernel runs
    natively on TPU and under ``interpret=True`` everywhere else).
    ``LIGHTGBM_TPU_DISABLE_PALLAS=1`` forces False — the operational
    kill switch the ``auto``/OOM-ladder fallback paths key on."""
    global _pallas_mod, _pallas_checked
    if os.environ.get("LIGHTGBM_TPU_DISABLE_PALLAS", "") == "1":
        return False
    if not _pallas_checked:
        _pallas_checked = True
        try:
            from jax.experimental import pallas as pl  # noqa: F401
            _pallas_mod = pl
        except Exception:  # pragma: no cover - env without pallas
            _pallas_mod = None
    return _pallas_mod is not None


def _row_tile(bp: int) -> int:
    """Rows per grid cell: the largest power of two <= 1024 keeping one
    feature's f32 one-hot ``[BP, RT]`` under the VMEM budget. Rows are
    the lane dimension, so the floor is 128 — reached at bp = 8192;
    wider than that the one-hot outgrows the budget and Mosaic decides
    whether it still fits."""
    rt = _ONEHOT_VMEM // (bp * 4)
    if rt < 128:
        return 128
    return min(1024, 1 << (rt.bit_length() - 1))


UNAVAILABLE_MSG = (
    "hist_method='pallas' requested but jax.experimental.pallas is "
    "unavailable (or LIGHTGBM_TPU_DISABLE_PALLAS=1); use "
    "hist_method='auto'|'mxu'|'scatter'")


def _require_pallas():
    """The imported pallas module, or a clear error when the kernel
    cannot be built here (single cache: pallas_available())."""
    if not pallas_available():
        raise RuntimeError(UNAVAILABLE_MSG)
    return _pallas_mod


def _hist_kernel(bins_ref, pay_ref, out_ref, *, bp: int, row_tile: int):
    """One (feature-pack, row-tile) grid cell.

    ``out_ref`` is the pack's [FPACK, C, BP] f32 accumulator — the same
    block for every row tile (the grid's innermost dimension), so it
    lives in VMEM across the whole row sweep."""
    pl = _require_pallas()
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = bins_ref[...].astype(jnp.int32)            # [FPACK, RT]
    pay = pay_ref[...]                                # [C, RT]
    iota_b = lax.broadcasted_iota(jnp.int32, (bp, row_tile), 0)
    for f in range(FPACK):
        # [C, BP] = pay @ onehot_t^T, contracting the row (lane)
        # dimension of both; the one-hot never leaves VMEM
        onehot_t = (bins[f:f + 1, :] == iota_b).astype(jnp.float32)
        out_ref[f] += lax.dot_general(pay, onehot_t,
                                      (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)


def _hist_tiles(rows: jnp.ndarray, payload: jnp.ndarray, num_bins: int,
                interpret: bool) -> jnp.ndarray:
    """One pallas_call over the whole [S, F] block -> [F, B, C] f32."""
    pl = _require_pallas()
    S, F = rows.shape
    C = payload.shape[-1]
    bp = max(128, -(-num_bins // 128) * 128)
    rt = _row_tile(bp)
    Sp = -(-S // rt) * rt
    Fp = -(-F // FPACK) * FPACK
    # pad rows carry a zero payload; pad features' histogram rows are
    # cropped below — their bin values are irrelevant
    bins_t = jnp.pad(rows.T, ((0, Fp - F), (0, Sp - S)))
    pay_t = jnp.pad(payload.astype(jnp.float32).T, ((0, 0), (0, Sp - S)))
    out = pl.pallas_call(
        functools.partial(_hist_kernel, bp=bp, row_tile=rt),
        grid=(Fp // FPACK, Sp // rt),
        in_specs=[
            pl.BlockSpec((FPACK, rt), lambda i, j: (i, j)),
            pl.BlockSpec((C, rt), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((FPACK, C, bp), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Fp, C, bp), jnp.float32),
        interpret=interpret,
    )(bins_t, pay_t)
    return jnp.transpose(out, (0, 2, 1))[:F, :num_bins, :]


def hist_from_rows_pallas(rows: jnp.ndarray, payload: jnp.ndarray,
                          num_bins: int, int_exact: bool = False,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Histogram over a row-block matrix via the Pallas kernel.

    Args:
      rows: ``[S, F]`` integer bin matrix (row-major, u8/u16).
      payload: ``[S, C]`` float channels, or int8 when ``int_exact``.
      num_bins: B.
      int_exact: accumulate an int8 payload to an EXACT int32 result
        (subtraction-safe) via <=INT_BLOCK-row super-blocks.
      interpret: run under the Pallas interpreter; defaults to True on
        every non-TPU backend (the tier-1 CPU parity mode) and is an
        error on a TPU, where the kernel is always compiled.

    Returns:
      ``[F, B, C]`` f32 (int32 when ``int_exact``).
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError("the Pallas histogram kernel does not run "
                         "interpreted on a TPU (interpret=True)")
    S = rows.shape[0]
    if not int_exact:
        return _hist_tiles(rows, payload, num_bins, interpret)
    if S <= INT_BLOCK:
        return _hist_tiles(rows, payload, num_bins,
                           interpret).astype(jnp.int32)
    nblk = -(-S // INT_BLOCK)
    pad = nblk * INT_BLOCK - S
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        payload = jnp.pad(payload, ((0, pad), (0, 0)))
    F = rows.shape[1]
    rows_b = rows.reshape(nblk, INT_BLOCK, F)
    pay_b = payload.reshape(nblk, INT_BLOCK, payload.shape[-1])

    def body(acc, xs):
        r, p = xs
        h = _hist_tiles(r, p, num_bins, interpret)
        return acc + h.astype(jnp.int32), None

    init = jnp.zeros((F, num_bins, payload.shape[-1]), jnp.int32)
    out, _ = lax.scan(body, init, (rows_b, pay_b))
    return out


# standalone jitted entry point: benchmarks/hist_micro.py's pallas arm
# and ad-hoc kernel probes dispatch through this, and registering it
# puts the kernel under the same recompile telemetry (TPL003 /
# obs/jit_tracker.py) as the other hot-path programs
hist_from_rows_pallas_jit = jax.jit(
    hist_from_rows_pallas,
    static_argnames=("num_bins", "int_exact", "interpret"))

from ..obs import register_jit  # noqa: E402  (after the jit exists)

hist_from_rows_pallas_jit = register_jit("ops/pallas_hist",
                                         hist_from_rows_pallas_jit,
                                         max_signatures=8)
