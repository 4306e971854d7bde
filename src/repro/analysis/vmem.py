"""Per-kernel VMEM footprint model, checked against the TPU budget.

Every Pallas kernel in `repro.kernels` stages block-spec tiles plus VMEM
scratch on chip; a candidate tiling whose working set exceeds the scoped
VMEM a kernel may use (`vmem_budget_bytes`) fails to lower (Mosaic
"not enough VMEM"-class errors) — previously discovered only by TIMING
the candidate inside `autotune.tune` and letting it lose.  This module computes the footprint
statically from the same quantities the launch uses (block shapes,
operand dtypes, scratch shapes), so:

  * `kernels/autotune.py` prunes infeasible candidates BEFORE timing
    (shorter tuning runs, and a class of Mosaic failures never launches);
  * the `python -m repro.analysis` VM rules verify the default/native
    tilings of every registered kernel and every persisted autotune
    cache entry against the budget.

The model counts, per operand and output, tile_bytes x 2 (Pallas
double-buffers pipelined tiles), scratch once, and the in-kernel f32
dequant temporaries the kernel bodies materialize.  It is deliberately a
LOWER bound — compiler-internal spills and fusions are not modeled — so
a candidate it rejects is certainly infeasible, while one it admits may
still lose in `tune` the old way (by failing to lower).  Never the
reverse: the model must not over-prune, which the soundness tests pin by
checking it admits every tiling the kernel suite actually launches.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.formats import FXPFormat, VPFormat
from repro.core.packing import storage_dtype
from repro.core.vp_tensor import significand_dtype

_MiB = 1024 * 1024
# Scoped VMEM one kernel may use without raising `vmem_limit_bytes`, by
# `device_kind`.  TPU v5e has 128 MiB of VMEM per core, of which the XLA
# TPU compiler scopes 16 MiB to a kernel by default (JAX Pallas TPU
# documentation; libtpu 0.0.34 refuses a 32 MiB kernel for a v5e with
# "Scoped allocation with size 32.00M and limit 16.00M").
_BUDGET_BY_KIND = {"TPU v5 lite": 16 * _MiB}
# Off the chip (CPU tests, interpret mode, compile rehearsals for a
# described v5e) the model keeps the v5e figure.
_OFF_CHIP_BUDGET = _BUDGET_BY_KIND["TPU v5 lite"]
_ENV_VAR = "REPRO_VMEM_BUDGET_BYTES"

# Online-softmax scratch rows are lane-broadcast to the TPU lane count
# (kernels/vp_attention._LANES).
_LANES = 128
_F32 = 4


def vmem_budget_bytes() -> int:
    """The VMEM budget of JAX's first device.

    `REPRO_VMEM_BUDGET_BYTES` overrides it.  A TPU whose `device_kind`
    is not in the table raises: a guessed budget would prune tilings the
    chip could run, or admit ones it refuses.
    """
    env = os.environ.get(_ENV_VAR)
    if env:
        return int(env)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _OFF_CHIP_BUDGET
    if dev.device_kind not in _BUDGET_BY_KIND:
        raise ValueError(
            f"no VMEM budget is known for {dev.device_kind!r}; add it to "
            f"repro.analysis.vmem._BUDGET_BY_KIND with its source, or set "
            f"{_ENV_VAR}")
    return _BUDGET_BY_KIND[dev.device_kind]


def _itemsize(dtype) -> int:
    return int(np.dtype(dtype).itemsize)


def _plane_bytes(fmt: VPFormat) -> int:
    """Bytes/element of the two-plane layout (significand + uint8 index)."""
    return _itemsize(significand_dtype(fmt.M)) + 1


def _word_bytes(fmt: VPFormat) -> int:
    """Bytes/element of the packed-word layout."""
    return _itemsize(storage_dtype(fmt))


def _vp(formats: Sequence, idx: int) -> Optional[VPFormat]:
    fs = [f for f in formats if isinstance(f, (VPFormat, FXPFormat))]
    if idx < len(fs) and isinstance(fs[idx], VPFormat):
        return fs[idx]
    return None


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _shard_shape(shape, shards):
    """Per-shard logical shape: each dim ceil-divided by its shard count."""
    if shape is None or shards is None:
        return shape
    if len(shards) != len(shape):
        raise ValueError(
            f"shards {tuple(shards)} must match shape rank {tuple(shape)}")
    return tuple(-(-int(d) // max(1, int(s)))
                 for d, s in zip(shape, shards))


def _lanes_bytes(gl: int, shape) -> Optional[int]:
    """The lane body of the batched fused kernel
    (`kernels/vp_quant_matmul._vp_quant_matmul_lanes_kernel`): `blocks`
    is (gl,), products a step; `shape` (G, M, K, N).  Float a (M·Kp, gl)
    and b (N·Kp, gl) in, (M·N, gl) out, K rounded up to 8 sublanes; b
    quantized once and one quantized (Kp, gl) slab of a live at a time."""
    if shape is None or len(shape) < 4:
        return None
    m, k, n = (int(d) for d in shape[-3:])
    kp = -(-k // 8) * 8
    in_bytes = (m * kp + n * kp) * gl * _F32
    out = m * n * gl * _F32
    temps = (n * kp + kp) * gl * _F32
    return 2 * in_bytes + 2 * out + temps


def kernel_vmem_bytes(
    kernel: str,
    blocks: Tuple[int, int, int],
    formats: Sequence = (),
    shape: Optional[Sequence[int]] = None,
    shards: Optional[Sequence[int]] = None,
) -> Optional[int]:
    """Static VMEM working set of one kernel launch, or None if this
    kernel's layout is not modeled (unknown kernels are never pruned).

    `kernel`, `blocks`, `formats`, `shape` are exactly the values the
    autotune cache keys carry, so the autotuner can consult the model
    with what it already has in hand.

    `shards` (same rank as `shape`) divides the logical shape by the
    mesh-shard counts first: under shard_map each device launches on its
    LOCAL operand, so tiles clamp to the per-shard dims (the same
    power-of-two clamp `heuristic_blocks` applies) — a tiling that only
    fits on-chip BECAUSE the mesh shrank the operand is admitted, and
    one whose per-shard tile still overflows is rejected.
    """
    if kernel == "vp_quant_matmul_batched_lanes":
        return _lanes_bytes(int(blocks[0]), shape)
    bm, bk, bn = int(blocks[0]), int(blocks[1]), int(blocks[2])
    if shards is not None and shape is not None:
        # Per-shard launch: the resolver re-clamps tiles to the LOCAL
        # operand (`heuristic_blocks`' power-of-two clamp), so the model
        # evaluates the tile that actually launches on each device —
        # never the single-device tile a shard could not even stage.
        shape = _shard_shape(shape, shards)
        if "attention" in kernel or "prefill" in kernel:
            if len(shape) >= 2:  # blocks[1] tiles the (sharded) seq dim
                bk = min(bk, _pow2_at_least(int(shape[1])))
        elif len(shape) >= 3:
            m, k, n = (int(d) for d in shape[-3:])
            bm = min(bm, _pow2_at_least(m))
            bk = min(bk, _pow2_at_least(k))
            bn = min(bn, _pow2_at_least(n))
    base = kernel.split("_bk")[0] if kernel.startswith(
        "block_vp_matmul") else kernel
    batched = "batched" in base
    base = base.replace("_batched", "")

    if base in ("vp_matmul", "vp_matmul_packed"):
        a_fmt, b_fmt = _vp(formats, 0), _vp(formats, 1)
        if a_fmt is None or b_fmt is None:
            return None
        if base.endswith("_packed"):
            in_bytes = bm * bk * _word_bytes(a_fmt) \
                + bk * bn * _word_bytes(b_fmt)
        else:
            in_bytes = bm * bk * _plane_bytes(a_fmt) \
                + bk * bn * _plane_bytes(b_fmt)
        temps = (bm * bk + bk * bn) * _F32          # dequantized tiles
        out = bm * bn * _F32
        scratch = bm * bn * _F32
        return 2 * in_bytes + 2 * out + scratch + temps

    if base == "vp_dequant_matmul":
        w_fmt = _vp(formats, 0)
        if w_fmt is None:
            return None
        in_bytes = bm * bk * _F32 + bk * bn * _word_bytes(w_fmt)
        temps = bk * bn * _F32                       # dequantized W tile
        out = bm * bn * _F32
        scratch = bm * bn * _F32
        return 2 * in_bytes + 2 * out + scratch + temps

    if base == "vp_matmul_dx":
        # g (bm, bn) f32 and packed-w (bk, bn) tiles in, (bm, bk) out
        # with an f32 accumulator scratch; the dequantized w tile is the
        # only temp (kernels/vp_bwd_matmul._vp_matmul_dx_kernel).
        w_fmt = _vp(formats, 0)
        if w_fmt is None:
            return None
        in_bytes = bm * bn * _F32 + bk * bn * _word_bytes(w_fmt)
        temps = bk * bn * _F32                       # dequantized W tile
        out = bm * bk * _F32
        scratch = bm * bk * _F32
        return 2 * in_bytes + 2 * out + scratch + temps

    if base == "vp_matmul_dw":
        # packed-a (bm, bk) and g (bm, bn) f32 tiles in, (bk, bn) out
        # with an f32 accumulator scratch; temp = dequantized a tile.
        a_fmt = _vp(formats, 0)
        if a_fmt is None:
            return None
        in_bytes = bm * bk * _word_bytes(a_fmt) + bm * bn * _F32
        temps = bm * bk * _F32                       # dequantized A tile
        out = bk * bn * _F32
        scratch = bk * bn * _F32
        return 2 * in_bytes + 2 * out + scratch + temps

    if base == "vp_quant_matmul":
        # Float operands in, quantize-dequantize cascade in-register:
        # int32 (m, i) intermediates per operand tile + the f32 results.
        in_bytes = (bm * bk + bk * bn) * _F32
        temps = (bm * bk + bk * bn) * _F32
        out = bm * bn * _F32
        scratch = bm * bn * _F32
        return 2 * in_bytes + 2 * out + scratch + temps

    if base == "block_vp_matmul":
        in_bytes = bm * bk + bk * bn + bm + bn       # int8 planes + indices
        temps = bm * bn * 4 + (bm + bn) * _F32       # int32 MXU tile, scales
        out = bm * bn * _F32
        scratch = bm * bn * _F32
        return 2 * in_bytes + 2 * out + scratch + temps

    if base == "vp_decode_attention":
        fmt = _vp(formats, 0)
        if fmt is None or shape is None or len(shape) < 4:
            return None
        dh = int(shape[3])
        bs = bk                                      # seq tile = blocks[1]
        rows = 8                                     # Gp floor (lower bound)
        cache = 2 * bs * dh * _word_bytes(fmt)       # K and V word tiles
        scales = 2 * bs * _F32
        q = rows * dh * _F32
        temps = 2 * bs * dh * _F32                   # dequantized K, V
        out = rows * dh * _F32
        scratch = (2 * rows * _LANES + rows * dh) * _F32
        return 2 * (cache + scales + q) + 2 * out + scratch + temps

    if base == "flash_prefill":
        if shape is None or len(shape) < 4:
            return None
        dh = int(shape[3])
        bq, bkv = bm, bk                             # blocks = (bq, bk, 1)
        in_bytes = (bq + 2 * bkv) * dh * _F32
        out = bq * dh * _F32
        scratch = (2 * bq * _LANES + bq * dh) * _F32
        temps = bq * bkv * _F32                      # scores tile
        return 2 * in_bytes + 2 * out + scratch + temps

    del batched  # per-tile footprint is batch-independent (leading 1)
    return None


def vmem_feasible(
    kernel: str,
    blocks: Tuple[int, int, int],
    formats: Sequence = (),
    shape: Optional[Sequence[int]] = None,
    budget: Optional[int] = None,
    shards: Optional[Sequence[int]] = None,
) -> Tuple[bool, Optional[int]]:
    """(fits, modeled bytes).  Unmodeled kernels report (True, None) —
    the autotuner must never prune what it cannot reason about."""
    need = kernel_vmem_bytes(kernel, blocks, formats, shape, shards=shards)
    if need is None:
        return True, None
    budget = vmem_budget_bytes() if budget is None else budget
    return need <= budget, need
