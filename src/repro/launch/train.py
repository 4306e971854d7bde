"""End-to-end training driver with checkpoint/restart + fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt [--quant vp]

It runs on a TPU and raises if JAX finds none, unless the CPU was asked
for with JAX_PLATFORMS=cpu, where it trains the reduced (smoke) configs
for tests and rehearsals; on a TPU fleet the same script runs the full
configs under the production mesh (--mesh prod).  Compiles persist in
`launch.runtime.enable_compile_cache`'s directory.
The loop is crash-contained: every step the data position advances
deterministically; on restart the latest INTACT checkpoint + data index
resume bit-exactly (`CheckpointManager.restore_latest` walks past any
checkpoint that fails its manifest checksums).

`--ft-sim` exercises the full fault-tolerance stack against a simulated
host set: each step every live simulated host heartbeats the
`FaultToleranceController` (a designated straggler reports 3x step
durations), `--ft-fail-steps` crashes one host at the named steps
(killing the loop with a RuntimeError), and `run_with_restarts`
restarts the loop — which resumes from the latest intact checkpoint
while the controller evicts the dead host and proposes a shrunken
elastic mesh.  The same controller/restart machinery a real fleet runs,
driven end-to-end on one process.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import QuantConfig
from repro.launch import runtime
from repro.models import init_params
from repro.optim import OptConfig, init_opt_state
from repro.optim.optimizer import OptState
from repro.train import make_train_step, CheckpointManager, \
    FaultToleranceController, run_with_restarts
from repro.train.compression import CompressionConfig, init_compressor_state
from repro.data import DataConfig, SyntheticLM


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b",
                    choices=registry.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true",
                    help="error-feedback DP gradient compression "
                         "(codec per --grad-codec)")
    ap.add_argument("--grad-codec", default="int8",
                    choices=["int8", "vp"],
                    help="gradient codec for --compress-grads: int8 "
                         "linear, or packed VP words + pow2 scale")
    ap.add_argument("--compress-moments", action="store_true",
                    help="store Adam mu/nu between steps as packed VP "
                         "words (sqrt(nu) encoding)")
    ap.add_argument("--qat", default="off",
                    choices=["off", "fake", "packed"],
                    help="quantization-aware fine-tune: every qdot "
                         "quantizes through the serving VP format — "
                         "'fake' = STE in the float graph, 'packed' = "
                         "packed-word Pallas forward AND backward")
    ap.add_argument("--quant", default="none",
                    choices=["none", "fxp", "vp", "vp_block"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ft-sim", action="store_true",
                    help="drive the FT controller + restart wrapper "
                         "with a simulated host set")
    ap.add_argument("--ft-hosts", type=int, default=4,
                    help="simulated host count for --ft-sim")
    ap.add_argument("--ft-fail-steps", default="",
                    help="comma-separated steps at which a simulated "
                         "host crashes (kills the loop; restarted)")
    ap.add_argument("--ft-straggler", type=int, default=-1,
                    help="simulated host id reporting 3x step durations")
    ap.add_argument("--ft-max-restarts", type=int, default=3)
    args = ap.parse_args()
    runtime.require_tpu()
    runtime.enable_compile_cache()

    quant = QuantConfig(mode=args.quant)
    cfg = (registry.get_smoke_config(args.arch, quant) if args.smoke
           else registry.get_config(args.arch, quant))
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10),
                        total_steps=args.steps,
                        moment_codec="vp" if args.compress_moments
                        else None)
    qat = (QuantConfig(mode="vp", qat_mode=args.qat)
           if args.qat != "off" else None)
    cmp_cfg = (CompressionConfig(codec=args.grad_codec)
               if args.compress_grads else False)
    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch))
    step_fn = jax.jit(make_train_step(
        cfg, opt_cfg, microbatches=args.microbatches,
        compress_grads=cmp_cfg, qat=qat))

    extra_batch = {}
    if cfg.family == "encdec":
        extra_batch["frames"] = jnp.zeros(
            (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        extra_batch["patches"] = jnp.zeros(
            (args.batch, cfg.n_patches, cfg.d_model), jnp.float32)

    # The checkpoint manager also lives OUTSIDE the restartable loop: an
    # in-process restart (unlike a real crash) leaves the previous
    # attempt's async writer thread alive, and a fresh manager would
    # sweep its half-written tmp dir out from under it — losing the very
    # checkpoint the restart needs.  One manager means `restore_latest`
    # joins the in-flight save before reading.
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    # FT simulation state lives OUTSIDE the restartable loop: the
    # controller's view of the fleet (and which hosts already died)
    # must survive a crash-restart, exactly as it does on a real fleet
    # where the controller is a separate service.
    ft = None
    sim = None
    if args.ft_sim:
        ft = FaultToleranceController(args.ft_hosts)
        sim = {
            "dead": set(),
            "pending": sorted({int(s) for s in
                               args.ft_fail_steps.split(",") if s.strip()}),
            "healthy": ft.healthy(),
            "now": 0.0,
        }
        if args.ckpt_dir is None:
            print("[ft] warning: --ft-sim without --ckpt-dir restarts "
                  "from step 0 every crash")

    def _ft_step(i: int) -> None:
        """One simulated fleet round: heartbeats, aging, crash injection."""
        sim["now"] += 1.0
        for h in range(args.ft_hosts):
            if h in sim["dead"]:
                continue
            dur = 0.3 if h == args.ft_straggler else 0.1
            ft.heartbeat(h, dur, now=sim["now"])
        ft.tick()
        if ft.topology_changed(sim["healthy"]):
            sim["healthy"] = ft.healthy()
            mesh = ft.propose_mesh(chips_per_host=1, model_axis=1)
            print(f"[ft] topology changed: healthy={sim['healthy']} "
                  f"-> elastic mesh {mesh} (generation {ft.generation})")
        if sim["pending"] and i >= sim["pending"][0]:
            sim["pending"].pop(0)
            live = [h for h in range(args.ft_hosts) if h not in sim["dead"]]
            victim = live[-1] if live else 0
            sim["dead"].add(victim)
            raise RuntimeError(
                f"simulated failure of host{victim} at step {i}")

    def train_loop(attempt: int = 0):
        if attempt:
            print(f"[restart] attempt {attempt}")
        start = 0
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt_state = init_opt_state(params, opt_cfg)
        cmp_state = (init_compressor_state(params)
                     if args.compress_grads else None)
        if mgr:
            template = {"params": params, "opt": opt_state._asdict()}
            if cmp_state is not None:
                template["cmp"] = cmp_state
            res = mgr.restore_latest(template)
            if res is not None:
                restored, manifest, s = res
                params = restored["params"]
                opt_state = OptState(**restored["opt"])
                if cmp_state is not None:
                    # resume the error-feedback residual too — dropping
                    # it re-injects one step's quantization error
                    # unbalanced
                    cmp_state = restored.get("cmp", cmp_state)
                start = manifest["extra"]["data_index"]
                print(f"[resume] from step {s}, data index {start}")

        t0 = time.time()
        for i in range(start, args.steps):
            batch = {**data.batch_at(i), **extra_batch}
            if args.compress_grads:
                params, opt_state, metrics, cmp_state = step_fn(
                    params, opt_state, batch, cmp_state)
            else:
                params, opt_state, metrics = step_fn(
                    params, opt_state, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                dt = time.time() - t0
                print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)")
            if ft is not None:
                _ft_step(i)
            if mgr and (i + 1) % args.ckpt_every == 0:
                state = {"params": params, "opt": opt_state._asdict()}
                if cmp_state is not None:
                    state["cmp"] = cmp_state
                mgr.save(i + 1, state, extra={"data_index": i + 1})
        if mgr:
            state = {"params": params, "opt": opt_state._asdict()}
            if cmp_state is not None:
                state["cmp"] = cmp_state
            mgr.save(args.steps, state, extra={"data_index": args.steps})
            mgr.wait()
        print("done.")

    if args.ft_sim:
        run_with_restarts(train_loop, max_restarts=args.ft_max_restarts)
    else:
        train_loop()


if __name__ == "__main__":
    main()
