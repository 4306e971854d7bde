"""Mesh sweep driver: sharded packed-VP datapath over (B, S, mesh shape).

    PYTHONPATH=src python -m benchmarks.sweep --out BENCH_pr8.json
    PYTHONPATH=src python -m benchmarks.sweep --smoke --out smoke.json

In the spirit of maxtext's `run-sweeps.py`: the PARENT process never
touches jax — each sweep point runs in a fresh subprocess whose
`XLA_FLAGS` pins `--xla_force_host_platform_device_count` to that
point's device count, so one driver binary sweeps mesh shapes that a
single jax process could never revisit (device count is fixed at
backend init).  Each point writes a config-stamped per-point JSON; the
parent folds every row into one aggregate report (`--out`), the file
committed as `BENCH_pr8.json`.

What each point measures, on a ("data", "model") best-effort mesh:

  mm_single       single-device `vp_dequant_matmul` oracle
  mm_gather       shard_map, packed words all-gathered then one full
                  matmul — the non-overlapped baseline (and the
                  JX-SHGATH anti-pattern: it re-materializes the full
                  weight on every device)
  mm_ring         shard_map collective matmul: per-chunk dequant-matmul
                  overlapped with the `ppermute` packed-word rotate
  attn_single     single-device packed-KV `vp_decode_attention`
  attn_seq_shard  shard_map with the KV cache sharded along S and
                  all-gathered as PACKED words + scales

Every sharded row asserts bit-identical outputs against its
single-device oracle INLINE (concatenation-only collectives on the ref
backend) — a sweep point that loses parity dies loudly rather than
reporting a speedup for wrong numbers.

Async-collective overlap flags: the TPU set maxtext ships (async
all-gather fusion + compute/collective overlap) is stamped into every
point's config as `tpu_async_flags`.  Each worker exports them via
LIBTPU_INIT_ARGS before its backend starts; only a TPU backend loads
libtpu and reads them, so `applied_async_flags` records whether the
worker's platform was a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# The overlap flag set from maxtext's sweep script (TPU-only: they ride
# LIBTPU_INIT_ARGS, never XLA_FLAGS, whose CPU parser rejects them).
TPU_ASYNC_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true")

# (tp, B, S, K, N): >= 3 mesh shapes, small + large (B, S) each.  The
# matmul is decode-shaped (M = B tokens in flight, K x N weight).  The
# (B, K, N) combinations are chosen in the BIT-STABLE regime of XLA's
# CPU dot: the inline parity asserts require the column-blocked dot
# (M, K, N/tp) to reduce over K in the same order as the full (M, K, N)
# dot, which XLA honors at these shapes for every swept tp but not
# everywhere (e.g. M=8, K=1024, N=2048 picks a different K strategy
# per N and drifts ~5e-8).  A grid edit that leaves the stable regime
# fails the assert loudly rather than benchmarking unverified numbers.
FULL_GRID = [(2, 8, 256, 256, 512), (4, 8, 256, 256, 512),
             (8, 8, 256, 256, 512),
             (2, 64, 1024, 2048, 4096), (4, 64, 1024, 2048, 4096),
             (8, 64, 1024, 2048, 4096)]
SMOKE_GRID = [(2, 4, 64, 128, 256)]


def _worker_env(tp: int) -> dict:
    env = dict(os.environ)
    flags = [f"--xla_force_host_platform_device_count={tp}"]
    prev = env.get("XLA_FLAGS", "")
    prev = " ".join(f for f in prev.split()
                    if "--xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = " ".join([prev] + flags).strip()
    return env


def run_point(tp: int, B: int, S: int, K: int, N: int,
              out_path: str, repeats: int) -> dict:
    cmd = [sys.executable, "-m", "benchmarks.sweep", "--worker",
           "--tp", str(tp), "--batch", str(B), "--seq", str(S),
           "--dims", f"{K}x{N}", "--repeats", str(repeats),
           "--out", out_path]
    subprocess.run(cmd, env=_worker_env(tp), check=True,
                   cwd=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
    with open(out_path, encoding="utf-8") as f:
        return json.load(f)


def main_parent(args) -> int:
    grid = SMOKE_GRID if args.smoke else FULL_GRID
    outdir = os.path.dirname(os.path.abspath(args.out)) or "."
    rows, points = [], []
    for tp, B, S, K, N in grid:
        t0 = time.perf_counter()
        point_path = os.path.join(
            outdir, f"sweep_tp{tp}_B{B}_S{S}.json")
        rep = run_point(tp, B, S, K, N, point_path, args.repeats)
        points.append(rep["config"])
        rows.extend(rep["rows"])
        print(f"# point tp={tp} B={B} S={S} done in "
              f"{time.perf_counter() - t0:.1f}s -> {point_path}")
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump({"rows": rows, "points": points}, f, indent=1)
        f.write("\n")
    print(f"# aggregate: {len(rows)} rows over {len(points)} points "
          f"-> {args.out}")
    return 0


# ---------------------------------------------------------------------------
# Worker: one (tp, B, S) point inside its own jax process
# ---------------------------------------------------------------------------

def _timeit(fn, n: int) -> float:
    """MIN wall-clock (us) over n runs; first call warms the compile."""
    fn()
    t = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        t = min(t, time.perf_counter() - t0)
    return t * 1e6


def main_worker(args) -> int:
    from functools import partial

    # libtpu reads LIBTPU_INIT_ARGS once, when a TPU backend starts in
    # this process; a CPU worker never loads libtpu, so the flags take
    # effect exactly when the worker's own platform is a TPU.
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(
        [os.environ.get("LIBTPU_INIT_ARGS", ""), TPU_ASYNC_FLAGS]).strip()

    # JAX is imported here and nowhere else in this module: the parent
    # must stay off JAX, because a process that has touched JAX holds
    # the accelerator and the worker it spawns could not reach it.
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import QuantConfig
    from repro.kernels import ops as kops
    from repro.launch.mesh import best_effort_mesh
    from repro.models.layers import canonical_formats
    from repro.parallel import shard_ops

    tp, B, S = args.tp, args.batch, args.seq
    K, N = (int(d) for d in args.dims.split("x"))
    mesh = best_effort_mesh(tp)
    fxp, vp = canonical_formats(QuantConfig(mode="vp"))
    rows = []

    def emit(name, us, derived):
        # dict rows, matching benchmarks/run.py
        rows.append({"name": name, "us_per_call": us, "derived": derived})
        print(f"{name},{us:.2f},{derived}")

    # ---- dequant matmul: single vs gather vs ring --------------------
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32) / K
    w_pk = kops.vp_quant(w, fxp, vp, packed=True)

    single = jax.jit(lambda a, b: kops.vp_dequant_matmul(a, b, vp))
    y_ref = np.asarray(single(x, w_pk))
    us_single = _timeit(lambda: single(x, w_pk).block_until_ready(),
                        args.repeats)
    emit(f"sweep_mm_single_tp{tp}_B{B}", us_single, f"K={K};N={N};tp=1")

    mode_us = {}
    for mode in ("gather", "ring"):
        fn = jax.jit(jax.shard_map(
            partial(shard_ops.sharded_dequant_matmul, fmt=vp, mode=mode),
            mesh=mesh, in_specs=(P(), P(None, "model")), out_specs=P(),
            check_vma=False))
        y = np.asarray(fn(x, w_pk))
        assert np.array_equal(y, y_ref), \
            f"mm {mode} mode lost bit parity at tp={tp} B={B} K={K} N={N}"
        mode_us[mode] = _timeit(
            lambda f=fn: f(x, w_pk).block_until_ready(), args.repeats)
    speed = mode_us["gather"] / mode_us["ring"]
    emit(f"sweep_mm_gather_tp{tp}_B{B}", mode_us["gather"],
         f"vs_single={us_single / mode_us['gather']:.2f}x;parity=bit")
    emit(f"sweep_mm_ring_tp{tp}_B{B}", mode_us["ring"],
         f"ring_vs_gather={speed:.2f}x;parity=bit")

    # ---- packed-KV decode attention: single vs seq-sharded -----------
    H, KV, dh = 8, 4, 64
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, dh), jnp.float32)
    k_f = jax.random.normal(jax.random.PRNGKey(3), (B, S, KV, dh),
                            jnp.float32)
    v_f = jax.random.normal(jax.random.PRNGKey(4), (B, S, KV, dh),
                            jnp.float32)
    k_w = kops.vp_quant(k_f, fxp, vp, packed=True)
    v_w = kops.vp_quant(v_f, fxp, vp, packed=True)
    ones = jnp.ones((B, S, 1, 1), jnp.float32)
    lens = jnp.full((B,), S, jnp.int32)

    attn = jax.jit(lambda *a: kops.vp_decode_attention(*a, vp))
    o_ref = np.asarray(attn(q, k_w, v_w, ones, ones, lens))
    us_attn = _timeit(
        lambda: attn(q, k_w, v_w, ones, ones, lens).block_until_ready(),
        args.repeats)
    emit(f"sweep_attn_single_tp{tp}_B{B}_S{S}", us_attn,
         f"KV={KV};dh={dh};tp=1")

    sh_attn = jax.jit(jax.shard_map(
        partial(shard_ops.sharded_decode_attention, fmt=vp, mode="seq"),
        mesh=mesh,
        in_specs=(P(), P(None, "model"), P(None, "model"),
                  P(None, "model"), P(None, "model"), P()),
        out_specs=P(), check_vma=False))
    o = np.asarray(sh_attn(q, k_w, v_w, ones, ones, lens))
    assert np.array_equal(o, o_ref), \
        f"seq-sharded attention lost bit parity at tp={tp} B={B} S={S}"
    us_sh = _timeit(
        lambda: sh_attn(q, k_w, v_w, ones, ones, lens).block_until_ready(),
        args.repeats)
    word_b = (vp.storage_bits + 7) // 8
    emit(f"sweep_attn_seqshard_tp{tp}_B{B}_S{S}", us_sh,
         f"parity=bit;gather_bytes/elem={word_b}(f32=4)")

    config = {
        "tp": tp, "B": B, "S": S, "K": K, "N": N,
        "mesh": dict(mesh.shape),
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "tpu_async_flags": TPU_ASYNC_FLAGS,
        "applied_async_flags": jax.default_backend() == "tpu",
    }
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump({"config": config, "rows": rows}, f, indent=1)
        f.write("\n")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m benchmarks.sweep",
        description="mesh-shape sweep for the sharded packed-VP datapath")
    p.add_argument("--out", default="BENCH_pr8.json")
    p.add_argument("--smoke", action="store_true",
                   help="one tiny point (CI dispatch check)")
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--tp", type=int, default=2, help=argparse.SUPPRESS)
    p.add_argument("--batch", type=int, default=8, help=argparse.SUPPRESS)
    p.add_argument("--seq", type=int, default=256, help=argparse.SUPPRESS)
    p.add_argument("--dims", default="2048x4096", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    return main_worker(args) if args.worker else main_parent(args)


if __name__ == "__main__":
    sys.exit(main())
