"""Substrate-layer kernel tests: fused vp_quant_matmul parity vs the ref
oracles, package-wide import smoke (catches Pallas API drift at collection
time), backend dispatch semantics, and CSPADE-mask parity between the
kernel and ref paths."""
import importlib
import pathlib
import pkgutil

import numpy as np
import jax.numpy as jnp
import pytest

import repro.kernels
from repro.core import FXPFormat, VPFormat, block_vp_quantize, vp_quantize
from repro.kernels import ops, ref, substrate

Y_FXP, Y_VP = FXPFormat(9, 1), VPFormat(7, (1, -1))
W_FXP, W_VP = FXPFormat(12, 11), VPFormat(7, (11, 9, 7, 6))


def rand(shape, scale, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_t(df=2, size=shape).astype(np.float32)
    return jnp.asarray(np.clip(x, -8, 8) * scale)


# ---------------------------------------------------------------------------
# import smoke / substrate hygiene
# ---------------------------------------------------------------------------

def test_kernels_package_imports():
    """Every module under repro.kernels imports cleanly — a bare
    `pltpu.CompilerParams` on jax 0.4.x (the seed crash) dies right here,
    at collection time, instead of deep inside an equalizer test."""
    pkg = repro.kernels
    mods = [m.name
            for m in pkgutil.iter_modules(pkg.__path__, pkg.__name__ + ".")]
    assert len(mods) >= 7, mods
    for name in mods:
        importlib.import_module(name)


def test_no_direct_compiler_params_outside_substrate():
    """Launch-plumbing guard: Pallas TPU symbols are referenced only in
    substrate.py; every kernel launches through `vp_pallas_call`."""
    root = pathlib.Path(repro.kernels.__path__[0])
    for p in sorted(root.glob("*.py")):
        if p.name == "substrate.py":
            continue
        text = p.read_text()
        assert "CompilerParams" not in text, p
        assert "PrefetchScalarGridSpec" not in text, p
        assert "pallas.tpu" not in text and "pallas import tpu" not in text, p


def test_resolve_backend_semantics():
    """interpret=True -> interpreter; None/False -> native only ON a TPU
    backend, ref everywhere else (explicit False must never force TPU
    lowering on CPU — the seed dispatch bug)."""
    assert substrate.resolve_backend(True) == "interpret"
    native_or_ref = "native" if substrate.on_tpu() else "ref"
    assert substrate.resolve_backend(None) == native_or_ref
    assert substrate.resolve_backend(False) == native_or_ref


def test_interpret_false_off_tpu_runs_every_op():
    """All five public ops accept an explicit interpret=False on any
    backend (the seed raised AttributeError/lowering errors on CPU)."""
    a = rand((64, 96), 0.9, 0)
    b = rand((96, 64), 0.02, 1)
    ta = vp_quantize(a, Y_FXP, Y_VP)
    tb = vp_quantize(b, W_FXP, W_VP)

    m, i = ops.vp_quant(a, Y_FXP, Y_VP, interpret=False)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(ta.m))
    out = ops.vp_dequant(m, i, Y_VP, interpret=False)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.vp_dequant_ref(ta.m, ta.i, Y_VP)))

    want = ref.vp_matmul_ref(ta.m, ta.i, tb.m, tb.i, Y_VP, W_VP)
    got = ops.vp_matmul(ta.m, ta.i, tb.m, tb.i, Y_VP, W_VP, interpret=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    got = ops.vp_quant_matmul(
        a, b, Y_FXP, Y_VP, W_FXP, W_VP, interpret=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    am, ai = block_vp_quantize(a, Y_FXP, Y_VP, block=32, axis=-1)
    bm, bi = block_vp_quantize(b, W_FXP, W_VP, block=32, axis=0)
    got = ops.block_vp_matmul(
        am, ai, bm, bi, Y_VP, W_VP, bk=32, blocks=(32, 32, 32),
        interpret=False)
    want = ref.block_vp_matmul_ref(am, ai, bm, bi, Y_VP, W_VP, bk=32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused vp_quant_matmul parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mkn", [(256, 256, 256), (100, 300, 50),
                                 (257, 129, 65)])
def test_fused_vp_quant_matmul_matches_refs(mkn):
    """Fused kernel (interpret mode) == vp_quant_ref on each operand
    followed by vp_matmul_ref, including ragged (padded) shapes."""
    M, K, N = mkn
    a = rand((M, K), 0.9, 2)
    b = rand((K, N), 0.02, 3)
    out_k = ops.vp_quant_matmul(
        a, b, Y_FXP, Y_VP, W_FXP, W_VP, interpret=True)
    a_m, a_i = ref.vp_quant_ref(a, Y_FXP, Y_VP)
    b_m, b_i = ref.vp_quant_ref(b, W_FXP, W_VP)
    out_r = ref.vp_matmul_ref(a_m, a_i, b_m, b_i, Y_VP, W_VP)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5)


def test_fused_matches_unfused_kernel_path():
    """Fused and unfused kernel paths agree (same cascades, no HBM trip)."""
    a = rand((128, 256), 0.9, 4)
    b = rand((256, 128), 0.02, 5)
    ta = vp_quantize(a, Y_FXP, Y_VP)
    tb = vp_quantize(b, W_FXP, W_VP)
    unfused = ops.vp_matmul(
        ta.m, ta.i, tb.m, tb.i, Y_VP, W_VP, blocks=(128, 128, 128),
        interpret=True)
    fused = ops.vp_quant_matmul(
        a, b, Y_FXP, Y_VP, W_FXP, W_VP, blocks=(128, 128, 128),
        interpret=True)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(unfused), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# CSPADE-mask parity: kernel vs ref
# ---------------------------------------------------------------------------

def _masked_case(seed):
    M = K = N = 512
    bm = bk = bn = 256
    a = rand((M, K), 0.9, seed)
    b = rand((K, N), 0.02, seed + 1)
    # Damp the second k-block of BOTH operands so its tile pairs fall below
    # the activity thresholds -> the masks genuinely mute that k step.
    damp_a = jnp.where(jnp.arange(K)[None, :] >= bk, 0.01, 1.0)
    damp_b = jnp.where(jnp.arange(K)[:, None] >= bk, 0.01, 1.0)
    a = a * damp_a
    b = b * damp_b
    a_m, a_i = ref.vp_quant_ref(a, Y_FXP, Y_VP)
    b_m, b_i = ref.vp_quant_ref(b, W_FXP, W_VP)
    a_act, b_act = ref.cspade_tile_masks(
        ref.vp_dequant_ref(a_m, a_i, Y_VP),
        ref.vp_dequant_ref(b_m, b_i, W_VP),
        bm, bk, bn, thresh_a=0.5, thresh_b=0.02)
    return a, b, (a_m, a_i, b_m, b_i), (a_act, b_act), (bm, bk, bn)


def test_cspade_masks_vp_matmul_kernel_vs_ref():
    a, b, planes, (a_act, b_act), tiles = _masked_case(6)
    a_m, a_i, b_m, b_i = planes
    # masks must actually mute something, or the test is vacuous
    assert int(np.asarray(a_act).sum()) < a_act.size \
        or int(np.asarray(b_act).sum()) < b_act.size
    out_k = ops.vp_matmul(
        a_m, a_i, b_m, b_i, Y_VP, W_VP,
        a_act=a_act, b_act=b_act, blocks=tiles, interpret=True)
    out_r = ref.vp_matmul_ref(
        a_m, a_i, b_m, b_i, Y_VP, W_VP,
        a_act=a_act, b_act=b_act, tiles=tiles)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5)


def test_cspade_masks_fused_kernel_vs_ref():
    """The fused kernel honours the same tile-activity masks."""
    a, b, _, (a_act, b_act), tiles = _masked_case(8)
    out_k = ops.vp_quant_matmul(
        a, b, Y_FXP, Y_VP, W_FXP, W_VP,
        a_act=a_act, b_act=b_act, blocks=tiles, interpret=True)
    out_r = ref.vp_quant_matmul_ref(
        a, b, Y_FXP, Y_VP, W_FXP, W_VP,
        a_act=a_act, b_act=b_act, tiles=tiles)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5)
