"""Equalizer driver: the paper's B-VP uplink equalizer over a 5G band,
slot after slot in a closed loop, checked against the plain reference.

Set-up makes a pool of slots on the device from the seed
(`reference.uplink.make_pool`), fixes each subcarrier's AGC gains from
the pool, builds one B-VP spec per subcarrier with those gains, and
compiles the slot step: every subcarrier's W, broadcast over the slot's
symbols, applied by `mimo.ofdm.equalize_wideband(how="flat")`, one
batched VP kernel launch for the whole band.

The window equalizes slot after slot from the pool, each to
`block_until_ready` of its estimates.  A time read from the host's
clock has to span a quarter second, and a slot takes less, so the slots
are timed in groups of consecutive slots that each span at least
`GROUP_S` (eight at 36 ms): the end-to-end metric is the 95th percentile
over the window's groups of their time per slot.  A sample of the window's
slots, drawn from the seed by reservoir sampling, keeps its estimates;
after the window each is compared with the reference B-VP equalizer
(`reference.uplink.vp_estimate`), and the worst NMSE and the worst
single error are held to the mix's limits.  A control run
(`bench.control`) judges the reference one significand bit down in the
program's place instead.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import stats, work
from bench.harness import Check, Context, Outcome, Readings, \
    memory_peak_bytes, seed_key
from bench.reference import uplink
from bench.spec import Cell

GROUP_S = 0.25          # least span of a group of slots timed together


def specs(cfg: dict, gw, gy):
    """One B-VP `EqualizerSpec` per subcarrier, with its gains."""
    from repro.core import FXPFormat, VPFormat
    from repro.mimo.equalizer import EqualizerSpec

    f = cfg["formats"]
    base = EqualizerSpec(
        "B-VP", True, FXPFormat(*f["y_fxp"]), FXPFormat(*f["w_fxp"]),
        VPFormat(f["y_vp"][0], tuple(f["y_vp"][1])),
        VPFormat(f["w_vp"][0], tuple(f["w_vp"][1])))
    return [dataclasses.replace(base, w_gain=float(a), y_gain=float(b))
            for a, b in zip(gw, gy)]


def slot_step(cfg: dict, band_specs):
    import jax
    import jax.numpy as jnp

    from repro.mimo.ofdm import equalize_wideband

    T = cfg["symbols_per_slot"]

    def step(w, y):
        S, U, B = w.shape
        wt = jnp.broadcast_to(w[:, None], (S, T, U, B))
        return equalize_wideband(band_specs, wt, y, how="flat")

    return jax.jit(step)


def run(cell: Cell, seed: int, seconds: float, ctx: Context) -> Outcome:
    import jax

    cfg, mix = cell.config, cell.traffic
    f = cfg["formats"]
    w_pool, y_pool = jax.jit(lambda k: uplink.make_pool(cfg, k))(
        seed_key(seed))
    h = cfg["agc_headroom"]
    gw = np.asarray(uplink.agc_gains(w_pool, *f["w_fxp"], h))
    gy = np.asarray(uplink.agc_gains(y_pool, *f["y_fxp"], h))
    P = cfg["slot_pool"]
    slots = [(w_pool[p], y_pool[p]) for p in range(P)]
    del w_pool, y_pool
    step = slot_step(cfg, specs(cfg, gw, gy))
    for w, y in slots[:2]:
        jax.block_until_ready(step(w, y))

    rng = np.random.default_rng([seed, 2])
    keep, n_keep = [], mix["check_slots"]
    calls = []
    ctx.open_window(seconds)
    clock = ctx.clock
    k = 0
    while True:
        now = clock.now()
        if now >= seconds:
            break
        ctx.tick(now)
        p = k % P
        with ctx.spans.span("slot"):
            t0 = clock.now()
            out = jax.block_until_ready(step(*slots[p]))
            calls.append((t0, clock.now()))
        # reservoir sample of the window's slots
        if len(keep) < n_keep:
            keep.append((p, out))
        else:
            j = int(rng.integers(0, k + 1))
            if j < n_keep:
                keep[j] = (p, out)
        k += 1
    took = clock.now()
    ctx.close_window()
    peak = memory_peak_bytes()

    groups = stats.group_times(calls, GROUP_S)
    e2e = {"eq_slot_p95_ms": 1e3 * stats.percentile(groups, 95)}
    ctx.log(f"window: {k} slots in {took:.4f} s ({1e3 * took / k:.4f} ms a "
            f"slot); {len(groups)} groups, p50 "
            f"{1e3 * stats.percentile(groups, 50):.4f} ms, p95 "
            f"{e2e['eq_slot_p95_ms']:.4f} ms a slot")
    call = work.equalizer_slot(cfg["subcarriers"], cfg["symbols_per_slot"],
                               cfg["users"], cfg["antennas"])
    readings = Readings(
        spans=list(ctx.spans.items), seconds=seconds, slots=k, slot_work=call,
        window=ctx.traced_window, compile_setup_s=ctx.compile_setup_s,
        trace=ctx.tracer.read() if ctx.tracer is not None else None)

    fmts = {"program": uplink.formats(cfg),
            "control": uplink.formats(cfg, "control")}
    est = {side: jax.jit(lambda w, y, a, b, f=f: uplink.vp_estimate(
        w, y, a, b, *f)) for side, f in fmts.items()}
    worst = {side: {"nmse": 0.0, "max_err": 0.0} for side in fmts}
    for p, out in keep:
        w, y = slots[p]
        ref = est["program"](w, y, gw, gy)
        got = {"program": out}
        if ctx.control:
            got["control"] = est["control"](w, y, gw, gy)
        for side, g in got.items():
            for name, v in uplink.errors(g, ref).items():
                worst[side][name] = uplink.worse(worst[side][name], v)
    limits = mix["limits"]

    def checks(side):
        return [Check("estimate_nmse", worst[side]["nmse"], limits["nmse"]),
                Check("estimate_max_err", worst[side]["max_err"],
                      limits["max_err"])]

    return Outcome(end_to_end=e2e, attempted=k, failed=0,
                   checks=checks("program"), readings=readings,
                   memory_peak_bytes=peak,
                   notes={"compared_slots": len(keep)},
                   control_checks=checks("control") if ctx.control else None)
