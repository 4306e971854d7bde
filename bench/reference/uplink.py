"""Wideband massive-MIMO uplink: the inputs of the equalizer cell and
its plain reference.

Inputs (`make_pool`), from the seed, on the device: per slot, a
line-of-sight mmWave channel per subcarrier (a uniform linear array at
half a wavelength; per user a direct path of Rician power K and a few
weak clusters around it; `n_taps` delay taps with an exponential power
delay profile, whose DFT across the band gives each subcarrier's
response), 16-QAM symbols on every (subcarrier, symbol, user), white
Gaussian noise at the configured SNR, all taken to beamspace by the
unitary DFT across the antennas, and the LMMSE matrix
W = (H^H H + N0 I)^-1 H^H of each (subcarrier, slot).

The reference is the paper's B-VP equalizer written from its
definition: the real and imaginary parts of W and y, each scaled by its
subcarrier's AGC gain, are rounded to the configuration's VP formats with
the benchmark's own VP arithmetic (`bench/reference/vp.py`), multiplied
in complex64 at `Precision.HIGHEST` (every product of two VP values is
exact in float32) and divided by the gains.  So the program is held to
what its format holds, and reads only the rounding of its sums.  The
control is the same equalizer one significand bit down: VP formats of
M - 1 bits over the same ranges (`control` in the configuration).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import vp

HIGHEST = jax.lax.Precision.HIGHEST
QAM = np.array([-3.0, -1.0, 1.0, 3.0]) / np.sqrt(10.0)


def _steering(b: int, sin_theta):
    n = jnp.arange(b, dtype=jnp.float32)
    return jnp.exp(1j * jnp.pi * sin_theta[..., None] * n).astype(
        jnp.complex64)


def los_channels(key, ch: dict, B: int, U: int, n: int):
    """n antenna-domain channels (n, B, U), unit mean gain per entry."""
    k_ang, k_cl, k_g, k_ph = jax.random.split(key, 4)
    C = ch["n_clusters"]
    s = jnp.sin(jnp.deg2rad(jax.random.uniform(
        k_ang, (n, U), minval=-ch["sector_deg"], maxval=ch["sector_deg"])))
    d_ang = jax.random.normal(k_cl, (n, U, C)) \
        * jnp.deg2rad(ch["angle_spread_deg"])
    s_cl = jnp.clip(s[..., None] + jnp.sin(d_ang), -1.0, 1.0)
    k_lin = 10.0 ** (ch["rician_k_db"] / 10.0)
    p_los = k_lin / (1.0 + k_lin)
    decay = jnp.exp(-jnp.arange(C) / 1.5)
    p_k = (1.0 - p_los) * decay / decay.sum()
    g = jax.random.normal(k_g, (n, U, C, 2)) * jnp.sqrt(0.5)
    g_cl = (g[..., 0] + 1j * g[..., 1]) * jnp.sqrt(p_k)
    phi = jax.random.uniform(k_ph, (n, U), maxval=2 * jnp.pi)
    g_los = jnp.sqrt(p_los) * jnp.exp(1j * phi)
    h = (g_los[..., None] * _steering(B, s)
         + jnp.einsum("nuc,nucb->nub", g_cl, _steering(B, s_cl)))
    return jnp.transpose(h, (0, 2, 1)).astype(jnp.complex64)


def _dft(b: int):
    n = np.arange(b)
    return jnp.asarray(np.exp(-2j * np.pi * np.outer(n, n) / b)
                       / np.sqrt(b), jnp.complex64)


def make_pool(cfg: dict, key):
    """`slot_pool` slots: W (P, S, U, B) and y (P, S, T, B), complex64,
    beamspace.  Call under `jax.jit`."""
    B, U, S, T = (cfg["antennas"], cfg["users"], cfg["subcarriers"],
                  cfg["symbols_per_slot"])
    P, ch = cfg["slot_pool"], cfg["channel"]
    L = ch["n_taps"]
    k_h, k_s, k_n = jax.random.split(key, 3)
    taps = jnp.stack([los_channels(k, ch, B, U, P)
                      for k in jax.random.split(k_h, L)])      # (L,P,B,U)
    pdp = jnp.exp(-jnp.arange(L) / ch["tap_decay"])
    taps = taps * jnp.sqrt(pdp / pdp.sum())[:, None, None, None]
    phase = jnp.exp(-2j * jnp.pi * jnp.outer(jnp.arange(S), jnp.arange(L))
                    / S).astype(jnp.complex64)                   # (S, L)
    F = _dft(B)
    h = jnp.einsum("sl,lpbu->psbu", phase, taps.astype(jnp.complex64))
    hb = jnp.einsum("ab,psbu->psau", F, h, precision=HIGHEST)
    n0 = 10.0 ** (-cfg["snr_db"] / 10.0)
    ki, kq = jax.random.split(k_s)
    lv = jnp.asarray(QAM, jnp.float32)
    sym = (lv[jax.random.randint(ki, (P, S, T, U), 0, 4)]
           + 1j * lv[jax.random.randint(kq, (P, S, T, U), 0, 4)])
    noise = jax.random.normal(k_n, (P, S, T, B, 2)) * np.sqrt(n0 / 2.0)
    y = (jnp.einsum("psbu,pstu->pstb", h, sym.astype(jnp.complex64),
                    precision=HIGHEST)
         + (noise[..., 0] + 1j * noise[..., 1]))
    yb = jnp.einsum("ab,pstb->psta", F, y.astype(jnp.complex64),
                    precision=HIGHEST)
    hh = jnp.conj(jnp.swapaxes(hb, -1, -2))                     # (P,S,U,B)
    gram = jnp.matmul(hh, hb, precision=HIGHEST)
    w = jnp.linalg.solve(gram + n0 * jnp.eye(U, dtype=gram.dtype), hh)
    return w.astype(jnp.complex64), yb.astype(jnp.complex64)


def fxp_max(word: int, frac: int) -> float:
    return (2 ** (word - 1) - 1) / 2 ** frac


def agc_gains(x, word: int, frac: int, headroom: float):
    """Per-subcarrier gain that maps the pool's largest |re| or |im| of
    that subcarrier to `headroom` of the fixed-point range.  x has the
    subcarrier axis second: (P, S, ...)."""
    amax = jnp.max(jnp.maximum(jnp.abs(x.real), jnp.abs(x.imag)),
                   axis=tuple(i for i in range(x.ndim) if i != 1))
    return headroom * fxp_max(word, frac) / jnp.maximum(amax, 1e-30)


def vp_estimate(w, y, gw, gy, w_fmt, y_fmt):
    """s = W y on VP operands: w (S, U, B), y (S, T, B) complex; gw, gy
    (S,) AGC gains; each format (W, F, M, f).  -> (S, T, U) complex64."""
    def q(x, g, fmt):
        g = g.reshape((-1,) + (1,) * (x.ndim - 1))
        return vp.grid(x.real * g, *fmt) + 1j * vp.grid(x.imag * g, *fmt)

    s = jnp.einsum("sub,stb->stu", q(w, gw, w_fmt), q(y, gy, y_fmt),
                   precision=HIGHEST)
    return s / (gw * gy)[:, None, None]


def formats(cfg: dict, part: str = "formats") -> tuple:
    """(W format, y format) as (W, F, M, f) from `cfg[part]`: the served
    formats, or the control's ("control"), over the same FXP grids."""
    f, vps = cfg["formats"], cfg[part]
    return tuple((*f[k + "_fxp"], vps[k + "_vp"][0], tuple(vps[k + "_vp"][1]))
                 for k in ("w", "y"))


def errors(got, ref) -> dict:
    """A slot's estimates against the reference: NMSE over the slot, and
    the largest single error over the RMS of the reference estimates."""
    got = np.asarray(got, np.complex128)
    ref = np.asarray(ref, np.complex128)
    err = np.abs(got - ref)
    power = np.mean(np.abs(ref) ** 2)
    return {"nmse": float(np.mean(err ** 2) / power),
            "max_err": float(np.max(err) / np.sqrt(power))}


def worse(a: float, b: float) -> float:
    """The larger reading; a NaN stays (it never passes a limit)."""
    return float("nan") if np.isnan(a) or np.isnan(b) else max(a, b)
