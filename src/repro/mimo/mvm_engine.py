"""The B-VP MVM engine proper: complex equalization through the Pallas
VP-matmul kernels (Fig. 9c / Fig. 10 as TPU kernel calls).

`equalizer.equalize_quantized` models the DESIGNS numerically (fake-quant
einsum — bit-identical values); this module runs the same computation
through the actual kernel path.  Two execution modes:

  * ``mode="batched"`` (default): the truly-batched kernel.  Realization
    g is its own (2U, B) x (B, 2) product — on the fused path one lane of
    a block of products, on the unfused path one tile program of the
    kernel's leading batch grid dimension — the A operand stacks the W
    re/im planes along rows and the B operand holds [y_re, y_im] as two
    columns, so ONE pallas_call produces all four real products of the
    paper's 4-RM complex-multiplier structure for every realization.
    FLOPs are 8·n·U·B, independent of how many realizations ride along.

  * ``mode="masked"`` (legacy, kept as the parity oracle): realizations
    are folded into a tall (n·U, B) x (B, n) matmul and the (row, col)
    pairs with col == row's realization are selected afterwards — n x
    wasted FLOPs/memory traffic (4·2·n²·U·B FLOPs), which is exactly the
    waste the batched grid removes.  `tests/test_batched_parity.py` pins
    the two modes bit-identical on every backend for mask-free runs
    (fused and unfused).  With CSPADE enabled the modes are NOT
    comparable bit-for-bit: the mask GEOMETRY differs by design —
    batched mutes per (realization, tile) on the stacked [W_re; W_im] /
    [y_re, y_im] operands, masked mutes tiles of the folded (nU, B) /
    (B, n) planes with thresholds sampled from the real planes only.

Both modes run the same quantize/dequant cascades:

  * FXP2VP conversion of the re/im planes (kernels.vp_quant), or the
    in-register fused cascade (kernels.vp_quant_matmul[_batched]);
  * complex MVM as 4 real VP products (the paper's 4-RM CM structure);
  * CSPADE tile-activity masks muting quiet tile pairs — per (batch,
    tile) in batched mode, i.e. whole quiet realizations get skipped.

Fused vs unfused dispatch (the `fused=None` default): the fused kernel is
chosen when (a) no CSPADE masks are requested — their calibration needs
the materialized planes; (b) the output-grid fan-out is small (<= 4 tiles
per output axis — the fused kernel re-quantizes each operand tile once
per opposing output tile, so past a few tiles the redundant cascade work
outgrows the saved HBM round-trip; batched MVM shapes are a single tile,
so they always qualify); and (c) a kernel backend is active (TPU-native
or interpret — the CPU ref path materializes planes regardless, so fusion
would only re-quantize shared operands).  Numerics are identical on every
path — same cascades throughout.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import FXPFormat, VPFormat
from repro.kernels import autotune, ops, ref, substrate
from .equalizer import EqualizerSpec


def _vp_planes(x, gain, fxp: FXPFormat, vp: VPFormat, interpret):
    return ops.vp_quant(x * gain, fxp, vp, interpret=interpret)


def _decision_tiles(blocks, M: int, K: int, N: int):
    """Tiles used for the fused-vs-unfused decision: the caller's explicit
    blocks, else the autotuner's shape-clamped heuristic (which is also
    what ops.py resolves to absent a tuned cache entry)."""
    return blocks if blocks is not None else autotune.heuristic_blocks(M, K, N)


def _div_tile(sz: int, target: int) -> int:
    """Largest divisor of `sz` that is <= target."""
    t = min(target, sz)
    while sz % t:
        t -= 1
    return t


def _mask_tiles(blocks, M: int, K: int, N: int):
    """Tile grid for the CSPADE paths: explicit blocks win; otherwise the
    heuristic snapped DOWN to exact divisors of the operand shape (mask
    construction reshapes on the grid, so tiles must divide exactly)."""
    if blocks is not None:
        return tuple(blocks)
    h = autotune.heuristic_blocks(M, K, N)
    return (_div_tile(M, h[0]), _div_tile(K, h[1]), _div_tile(N, h[2]))


def _pick_fused(fused: Optional[bool], cspade_q, nm: int, nn: int,
                interpret) -> bool:
    """The fused-vs-unfused dispatch policy (see module docstring)."""
    if fused is not None:
        return fused
    return (cspade_q is None
            and max(nm, nn) <= 4
            and substrate.resolve_backend(interpret) != "ref")


def _rpad(g, ndim: int):
    """Right-pad a gain's shape with 1s to broadcast over trailing dims."""
    g = jnp.asarray(g, jnp.float32)
    return g.reshape(g.shape + (1,) * (ndim - g.ndim))


@jax.jit
def stack_complex_operands(w, y, w_gain=1.0, y_gain=1.0):
    """Pack a complex MVM batch into the 4-RM batched-kernel operands.

    w (..., U, B) complex, y (..., B) complex; gains are scalars or
    arrays broadcasting over the LEADING dims (e.g. per-subcarrier (S,)
    for (S, n, U, B) operands — gains ride outside the quantizer, so
    they fold into the operands here and divide back out of the
    products).  Returns a (..., 2U, B) = [W_re; W_im] rows and
    b (..., B, 2) = [y_re, y_im] columns — the single source of truth
    for the packing shared by the narrowband engine and the wideband
    OFDM path.  Jitted: eagerly this is ~10 dispatched ops per call on
    the serving hot path; fused it is one.
    """
    wg = _rpad(w_gain, w.ndim)
    yg = _rpad(y_gain, y.ndim)
    wr = w.real.astype(jnp.float32) * wg
    wi = w.imag.astype(jnp.float32) * wg
    yr = y.real.astype(jnp.float32) * yg
    yi = y.imag.astype(jnp.float32) * yg
    a = jnp.concatenate([wr, wi], axis=-2)           # (..., 2U, B)
    b = jnp.stack([yr, yi], axis=-1)                 # (..., B, 2)
    return a, b


@jax.jit
def combine_products(out, gain=1.0):
    """(..., 2U, 2) raw 4-RM products -> complex (..., U) estimates.

    `gain` is the w_gain*y_gain product (scalar or broadcastable over
    the leading dims) divided back out of the physical-unit estimate.
    """
    U = out.shape[-2] // 2
    g = _rpad(gain, out.ndim - 1)
    re = (out[..., :U, 0] - out[..., U:, 1]) / g     # Wr yr - Wi yi
    im = (out[..., :U, 1] + out[..., U:, 0]) / g     # Wr yi + Wi yr
    return re + 1j * im


def batched_complex_mvm(
    a: jax.Array,            # (G, 2U, B) float — stacked [W_re; W_im] rows
    b: jax.Array,            # (G, B, 2) float — [y_re, y_im] columns
    fxp_w: FXPFormat, vp_w: VPFormat,
    fxp_y: FXPFormat, vp_y: VPFormat,
    cspade_threshold_quantile: Optional[float] = None,
    interpret: Optional[bool] = None,
    fused: Optional[bool] = None,
    blocks: Optional[tuple] = None,
) -> jax.Array:
    """All four real products of G complex MVMs in ONE batched kernel call.

    Operands are already AGC-scaled into the hardware formats' ranges.
    Returns the raw (G, 2U, 2) product tensor; with U = rows/2:
      out[:, :U, 0] = W_re y_re   out[:, :U, 1] = W_re y_im
      out[:, U:, 0] = W_im y_re   out[:, U:, 1] = W_im y_im
    This is the entry point the wideband OFDM path folds subcarriers into
    (mimo/ofdm.py): anything expressible as a batch of complex MVMs rides
    the same batched kernel (on the fused path, one product a lane).

    `blocks=None` defers the tile choice to the autotuner (ops.py resolves
    a tuned cache entry, else the shape-clamped heuristic).  The mask-free
    unfused path quantizes to PACKED VP words — one HBM plane per operand.
    """
    G, M, K = a.shape
    N = b.shape[-1]
    dt = _decision_tiles(blocks, M, K, N)
    fused = _pick_fused(fused, cspade_threshold_quantile,
                        -(-M // dt[0]), -(-N // dt[2]), interpret)

    if fused:
        if cspade_threshold_quantile is not None:
            raise ValueError(
                "fused path has no materialized planes to calibrate masks on")
        return ops.vp_quant_matmul_batched(
            a, b, fxp_w, vp_w, fxp_y, vp_y,
            blocks=blocks, interpret=interpret)

    if cspade_threshold_quantile is None:
        # Packed words: half the quantized-operand HBM traffic, outputs
        # bit-identical to the two-plane path (tests/test_packing.py).
        a_w = ops.vp_quant(a, fxp_w, vp_w, interpret=interpret, packed=True)
        b_w = ops.vp_quant(b, fxp_y, vp_y, interpret=interpret, packed=True)
        return ops.vp_matmul_batched(
            a_w, None, b_w, None, vp_w, vp_y,
            blocks=blocks, interpret=interpret)

    # CSPADE calibration needs materialized (m, i) planes, and the masks
    # pin the tile grid — resolve it here and pass it down explicitly.
    tiles = _mask_tiles(blocks, M, K, N)
    a_m, a_i = ops.vp_quant(a, fxp_w, vp_w, interpret=interpret)
    b_m, b_i = ops.vp_quant(b, fxp_y, vp_y, interpret=interpret)

    q = cspade_threshold_quantile
    ta = jnp.quantile(jnp.abs(a), q)
    tb = jnp.quantile(jnp.abs(b), q)
    a_deq = ref.vp_dequant_ref(a_m, a_i, vp_w)
    b_deq = ref.vp_dequant_ref(b_m, b_i, vp_y)
    a_act, b_act = ref.cspade_tile_masks_batched(
        a_deq, b_deq, *tiles, ta, tb)

    return ops.vp_matmul_batched(
        a_m, a_i, b_m, b_i, vp_w, vp_y,
        a_act=a_act, b_act=b_act, blocks=tiles, interpret=interpret)


def _equalize_batched(
    spec: EqualizerSpec, w, y, cspade_threshold_quantile, interpret, fused,
    blocks=None,
):
    a, b = stack_complex_operands(w, y, spec.w_gain, spec.y_gain)
    out = batched_complex_mvm(
        a, b, spec.w_fxp, spec.w_vp, spec.y_fxp, spec.y_vp,
        cspade_threshold_quantile=cspade_threshold_quantile,
        interpret=interpret, fused=fused, blocks=blocks)
    return combine_products(out, spec.w_gain * spec.y_gain)   # (n, U)


def _equalize_masked(
    spec: EqualizerSpec, w, y, cspade_threshold_quantile, interpret, fused,
    blocks=None,
):
    """Legacy masked-diagonal path (the PR-1 engine), kept as the parity
    oracle for the batched grid: fold realizations into the row axis, run
    (nU, B) x (B, n), select each row's own realization column."""
    n, U, B = w.shape
    fxp_y, vp_y = spec.y_fxp, spec.y_vp
    fxp_w, vp_w = spec.w_fxp, spec.w_vp

    wr = w.real.reshape(n * U, B).astype(jnp.float32)
    wi = w.imag.reshape(n * U, B).astype(jnp.float32)
    yr = y.real.T.astype(jnp.float32)   # (B, n)
    yi = y.imag.T.astype(jnp.float32)

    M, K = wr.shape
    N = yr.shape[1]
    dt = _decision_tiles(blocks, M, K, N)
    fused = _pick_fused(fused, cspade_threshold_quantile,
                        -(-M // dt[0]), -(-N // dt[2]), interpret)

    if fused:
        if cspade_threshold_quantile is not None:
            raise ValueError(
                "fused path has no materialized planes to calibrate masks on")

        def mmf(a_f, b_f):
            return ops.vp_quant_matmul(
                a_f, b_f, fxp_w, vp_w, fxp_y, vp_y,
                blocks=blocks, interpret=interpret)

        wrg, wig = wr * spec.w_gain, wi * spec.w_gain
        yrg, yig = yr * spec.y_gain, yi * spec.y_gain
        rr = mmf(wrg, yrg)    # (nU, n)
        ii = mmf(wig, yig)
        ri = mmf(wrg, yig)
        ir = mmf(wig, yrg)
    elif cspade_threshold_quantile is None:
        # Mask-free unfused: packed word planes (one HBM plane each).
        def _packed(x, gain, fxp, vp):
            return ops.vp_quant(
                x * gain, fxp, vp, interpret=interpret, packed=True)

        wr_w = _packed(wr, spec.w_gain, fxp_w, vp_w)
        wi_w = _packed(wi, spec.w_gain, fxp_w, vp_w)
        yr_w = _packed(yr, spec.y_gain, fxp_y, vp_y)
        yi_w = _packed(yi, spec.y_gain, fxp_y, vp_y)

        def mmp(aw, bw):
            return ops.vp_matmul(aw, None, bw, None, vp_w, vp_y,
                                 blocks=blocks, interpret=interpret)

        rr = mmp(wr_w, yr_w)    # (nU, n)
        ii = mmp(wi_w, yi_w)
        ri = mmp(wr_w, yi_w)
        ir = mmp(wi_w, yr_w)
    else:
        tiles = _mask_tiles(blocks, M, K, N)
        wr_m, wr_i = _vp_planes(wr, spec.w_gain, fxp_w, vp_w, interpret)
        wi_m, wi_i = _vp_planes(wi, spec.w_gain, fxp_w, vp_w, interpret)
        yr_m, yr_i = _vp_planes(yr, spec.y_gain, fxp_y, vp_y, interpret)
        yi_m, yi_i = _vp_planes(yi, spec.y_gain, fxp_y, vp_y, interpret)

        q = cspade_threshold_quantile
        ta = jnp.quantile(jnp.abs(wr) * spec.w_gain, q)
        tb = jnp.quantile(jnp.abs(yr) * spec.y_gain, q)
        Wd = ref.vp_dequant_ref(wr_m, wr_i, vp_w) * spec.w_gain
        Yd = ref.vp_dequant_ref(yr_m, yr_i, vp_y) * spec.y_gain
        a_act, b_act = ref.cspade_tile_masks(Wd, Yd, *tiles, ta, tb)

        def mm(am, ai, bm_, bi):
            return ops.vp_matmul(am, ai, bm_, bi, vp_w, vp_y,
                                 a_act=a_act, b_act=b_act, blocks=tiles,
                                 interpret=interpret)

        rr = mm(wr_m, wr_i, yr_m, yr_i)    # (nU, n)
        ii = mm(wi_m, wi_i, yi_m, yi_i)
        ri = mm(wr_m, wr_i, yi_m, yi_i)
        ir = mm(wi_m, wi_i, yr_m, yr_i)

    re = (rr - ii) / (spec.w_gain * spec.y_gain)
    im = (ri + ir) / (spec.w_gain * spec.y_gain)
    # select each row's own realization column
    rows = jnp.arange(n * U)
    cols = rows // U
    s = re[rows, cols] + 1j * im[rows, cols]
    return s.reshape(n, U)


def equalize_vp_kernel(
    spec: EqualizerSpec,
    w: jax.Array,            # (n, U, B) complex
    y: jax.Array,            # (n, B) complex
    cspade_threshold_quantile: Optional[float] = None,
    interpret: Optional[bool] = None,
    fused: Optional[bool] = None,
    mode: str = "batched",
    blocks: Optional[tuple] = None,
) -> jax.Array:
    """s_hat (n, U) complex through the VP kernel path.

    `mode` selects the execution strategy (see module docstring):
    "batched" runs each realization as its own tile program on the batched
    kernel grid; "masked" is the legacy folded (nU, B) x (B, n) matmul
    with diagonal selection.  Mask-free runs are bit-identical across
    modes (batched does 1/n of the work); with
    `cspade_threshold_quantile` set, each mode mutes on its own tile
    geometry and the outputs may differ within the muting perturbation.
    `blocks=None` defers tiling to the autotuner (see kernels.autotune).
    """
    assert spec.is_vp
    if mode == "batched":
        return _equalize_batched(
            spec, w, y, cspade_threshold_quantile, interpret, fused, blocks)
    if mode == "masked":
        return _equalize_masked(
            spec, w, y, cspade_threshold_quantile, interpret, fused, blocks)
    raise ValueError(f"unknown mode {mode!r} (want 'batched' or 'masked')")


def mvm_flops(n: int, U: int, B: int, mode: str = "batched") -> int:
    """Real-MAC FLOP count of one complex equalization batch.

    batched: 4 real products of (U, B)·(B,) per realization = 8·n·U·B.
    masked:  4 folded (nU, B) x (B, n) matmuls = 8·n²·U·B — the n x
    overhead the batched grid removes.
    """
    if mode == "batched":
        return 8 * n * U * B
    if mode == "masked":
        return 8 * n * n * U * B
    raise ValueError(f"unknown mode {mode!r}")
