"""The serving-path kernels compile for a TPU v5e at qwen3-0.6b's widths.

Nothing runs: each test lowers a kernel op on the native Pallas path for
one chip of a described (not attached) v5e topology and compiles it with
the TPU compiler installed alongside JAX, so Mosaic's tiling and VMEM
refusals surface here instead of on the chip.  Every compiled program
must hold the Pallas kernel (`tpu_custom_call`).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import QuantConfig
from repro.core.packing import storage_dtype
from repro.kernels import ops, substrate
from repro.models.attention import kv_cache_formats
from repro.models.layers import canonical_formats

# qwen3-0.6b: d_model 1024, 16 query heads and 8 KV heads of 128, d_ff
# 3072, vocab 151936; decode at 8 slots with a 576-position cache.
D, H, KV, DH, FF, V = 1024, 16, 8, 128, 3072, 151936
SLOTS, SMAX, PROMPT = 8, 576, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_native(one_chip):
    """Compile `fn` for one described chip; return the HLO text.

    A compile for a described chip cannot be read back from JAX's
    persistent cache without the chip, so the cache is off meanwhile.
    """
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        with substrate.force_backend("native"):
            return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("M,K,N", [
    (SLOTS, D, 2 * D),      # decode q projection
    (SLOTS, FF, D),         # decode MLP down projection
    (SLOTS, D, V),          # decode LM head
    (PROMPT, D, FF),        # prefill MLP up projection
])
def test_vp_dequant_matmul_compiles(compile_native, M, K, N):
    _, vp = canonical_formats(QuantConfig(mode="vp"))
    hlo = compile_native(
        lambda x, w: ops.vp_dequant_matmul(x, w, vp),
        ((M, K), jnp.bfloat16), ((K, N), storage_dtype(vp)))
    assert "tpu_custom_call" in hlo


def test_vp_decode_attention_compiles(compile_native):
    _, vp = kv_cache_formats(QuantConfig(mode="vp", quantize_kv_cache=True))
    words = ((SLOTS, SMAX, KV, DH), storage_dtype(vp))
    scales = ((SLOTS, SMAX, 1, 1), jnp.float32)
    hlo = compile_native(
        lambda q, kw, vw, ks, vs, n: ops.vp_decode_attention(
            q, kw, vw, ks, vs, n, vp),
        ((SLOTS, 1, H, DH), jnp.bfloat16), words, words, scales, scales,
        ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_flash_prefill_compiles(compile_native):
    hlo = compile_native(
        ops.flash_prefill,
        ((1, PROMPT, H, DH), jnp.bfloat16), ((1, PROMPT, KV, DH), jnp.bfloat16),
        ((1, PROMPT, KV, DH), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_equalizer_kernel_compiles(compile_native):
    """Table-I B-VP equalization of 1024 realizations (B=64, U=8) on the
    fused batched kernel."""
    from repro.mimo import table1_specs
    from repro.mimo.mvm_engine import equalize_vp_kernel

    spec = table1_specs()[2]
    n, U, B = 1024, 8, 64
    hlo = compile_native(
        lambda w, y: equalize_vp_kernel(spec, w, y, fused=True),
        ((n, U, B), jnp.complex64), ((n, B), jnp.complex64))
    assert "tpu_custom_call" in hlo


# The serving cell's paged cache: 28 layers of pools, pages of 16,
# capacity 2048 at 16 slots (16 x 128 pages and the dummy page).
LAYERS, PAGE, CAP, ROWS = 28, 16, 2048, 16


@pytest.mark.parametrize("steps,E,kv", [
    (1, 2, KV),
    (4, 2, KV),
    # other layouts the runner reads in place (16- and 8-bit words)
    (1, 2, 2),
    (1, 2, 24),
    (1, 1, 4),
    (4, 1, 32),
])
def test_vp_paged_decode_attention_compiles(compile_native, steps, E, kv):
    _, vp = kv_cache_formats(QuantConfig(mode="vp", quantize_kv_cache=True,
                                         E=E))
    with substrate.force_backend("native"):
        assert ops.paged_decode_supported(PAGE, kv, DH, storage_dtype(vp))
    n_pages = 1 + ROWS * CAP // PAGE
    words = ((LAYERS, n_pages, PAGE, kv, DH), storage_dtype(vp))
    scales = ((LAYERS, n_pages, PAGE, 1, 1), jnp.float32)
    tail = ((ROWS, steps, kv, DH), storage_dtype(vp))
    tail_s = ((ROWS, steps, 1, 1), jnp.float32)
    rows = ((ROWS,), jnp.int32)
    hlo = compile_native(
        lambda *a: ops.vp_paged_decode_attention(*a, vp),
        ((ROWS, 1, max(H, kv), DH), jnp.bfloat16), words, words, scales,
        scales, tail, tail, tail_s, tail_s, ((), jnp.int32),
        ((ROWS, CAP // PAGE), jnp.int32), rows, rows)
    assert "tpu_custom_call" in hlo
    # the pools reach the kernel whole: no copy of a layer's words
    assert f"[1,{n_pages},{PAGE},{kv},{DH}]" not in hlo


def _decode_program(one_chip, in_place: bool):
    """The runner's `decode_b16_s1` program of a two-layer model at
    qwen3-0.6b's head widths, compiled for one described chip."""
    from repro.configs.base import ModelConfig
    from repro.models import init_params, quantize_params
    from repro.serving.page_cache import PagedKVCache
    from repro.serving.runner import ModelRunner

    cfg = ModelConfig(name="two-layer", family="dense", n_layers=2,
                      d_model=256, n_heads=4, n_kv_heads=2, d_head=DH,
                      d_ff=512, vocab=512, qk_norm=True,
                      quant=QuantConfig(mode="vp", quantize_kv_cache=True,
                                        kv_layout="packed"))
    kv = PagedKVCache(cfg, max_slots=ROWS, capacity=256, page_size=PAGE)
    runner = ModelRunner(cfg, kv)
    assert runner.paged_decode
    runner.paged_decode = in_place
    params = jax.eval_shape(lambda: quantize_params(
        init_params(jax.random.PRNGKey(0), cfg), cfg))

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    args = jax.tree_util.tree_map(spec, (
        params, jnp.zeros((ROWS, 1), jnp.int32), kv.pools, kv.dense,
        kv.block_table, kv.lengths, jnp.arange(ROWS, dtype=jnp.int32),
        jnp.ones((ROWS,), bool), jax.random.PRNGKey(0)))
    with substrate.force_backend("native"):
        lowered = runner._make_decode(ROWS, 1).lower(*args)
        return lowered.as_text(), lowered.compile().as_text()


def test_paged_decode_program_gathers_no_view(compile_native, one_chip):
    """The in-place decode program holds no capacity-length view of the
    pools, before or after the TPU compiler; the gathered path, compiled
    the same way, does (the check can see one)."""
    view_hlo = f"s16[2,{ROWS},256,2,{DH}]"
    view_mlir = f"tensor<2x{ROWS}x256x2x{DH}xi16>"
    pool = f"tensor<2x{1 + ROWS * 256 // PAGE}x{PAGE}x"   # words, scales

    def pool_gathers(text):
        return [line for line in text.splitlines()
                if "stablehlo.gather" in line and pool in line]

    lowered, compiled = _decode_program(one_chip, in_place=True)
    assert "tpu_custom_call" in compiled
    assert "vp_paged_decode_attention" in compiled
    assert view_mlir not in lowered and view_hlo not in compiled
    assert pool_gathers(lowered) == []
    lowered, compiled = _decode_program(one_chip, in_place=False)
    assert view_mlir in lowered and view_hlo in compiled
    assert len(pool_gathers(lowered)) == 4
