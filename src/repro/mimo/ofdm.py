"""Wideband OFDM equalization on the truly-batched VP kernel grid.

The paper's workload is one LMMSE MVM per symbol time; a real wideband
system runs that MVM on EVERY OFDM subcarrier of every symbol — S
independent (U, B) x (B,) products per channel use (cf. "Customizing
Number Representation and Precision", Sentieys & Menard 2022, on
per-signal format tuning at scale).  This module grows the narrowband
demo into that serving-shaped pipeline:

  * `generate_wideband_channels`: tapped-delay-line extension of the LoS
    mmWave generator — L delay taps with an exponential power-delay
    profile, DFT across taps gives per-subcarrier frequency responses
    H[s] (correlated across s, like a real frequency-selective channel);
  * `make_wideband_ensemble`: per-subcarrier 16-QAM symbols, AWGN,
    beamspace transform, and LMMSE matrices — shapes carry a leading
    subcarrier axis (S, n, ...);
  * `WidebandCalibrator`: cached per-subcarrier calibration — AGC gains
    per subcarrier (beamspace statistics drift across the band) and,
    optionally, per-subcarrier VP exponent-list selection through
    `core.param_search` (paper Sec. II-D run once per subcarrier, cached
    so repeated symbols/frames reuse the search);
  * `equalize_wideband`: the execution path.  All (subcarrier,
    realization) MVMs fold into ONE leading batch grid dimension of the
    batched VP kernel (`mvm_engine.batched_complex_mvm`) — per-subcarrier
    AGC gains are applied to the operands up front and divided out of the
    products, so a single fused pallas_call serves the whole band.
    `how="vmap"` maps the same computation over the subcarrier axis, and
    `how="shard_map"` shards it over a device mesh axis via
    `parallel.sharding.shard_over_subcarriers` — the fleet-scale layout
    where each device owns a slab of the band.

Execution-path equivalence: the gains ride OUTSIDE the quantizer in every
path (scale in, quantize, divide out), so flat / vmap / shard_map produce
bit-identical estimates; `tests/test_ofdm.py` pins this.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import VPFormat, param_search
from .channel import ChannelConfig, generate_channels, awgn
from .beamspace import to_beamspace
from .lmmse import lmmse_matrix
from .equalizer import EqualizerSpec, calibrate
from .mvm_engine import (
    batched_complex_mvm, combine_products, stack_complex_operands,
)
from .sim import qam16_mod


@dataclasses.dataclass(frozen=True)
class OFDMConfig:
    """Wideband dimensioning: S subcarriers over an L-tap delay channel."""

    n_subcarriers: int = 16
    n_taps: int = 4             # delay taps (frequency selectivity)
    tap_decay: float = 1.5      # exponential power-delay-profile constant

    @property
    def S(self) -> int:
        return self.n_subcarriers


def generate_wideband_channels(
    key, cfg: ChannelConfig, ofdm: OFDMConfig, n: int,
) -> jax.Array:
    """Per-subcarrier channels H[s], shape (S, n, B, U) complex64.

    Tapped-delay-line model: each tap is an independent draw of the LoS
    mmWave geometry (same UE population statistics), weighted by an
    exponential power-delay profile; the frequency response at subcarrier
    s is the DFT of the taps, H[s] = sum_l h_l * exp(-2pi*j*s*l/S).
    Power is normalized so E[|H|^2] per antenna matches the narrowband
    generator (the per-stream SNR convention is unchanged).
    """
    L, S = ofdm.n_taps, ofdm.S
    keys = jax.random.split(key, L)
    taps = jnp.stack(
        [generate_channels(k, cfg, n) for k in keys])      # (L, n, B, U)
    pdp = jnp.exp(-jnp.arange(L) / ofdm.tap_decay)
    pdp = pdp / pdp.sum()                                  # unit total power
    taps = taps * jnp.sqrt(pdp)[:, None, None, None].astype(taps.dtype)
    phase = jnp.exp(
        -2j * jnp.pi * jnp.outer(jnp.arange(S), jnp.arange(L)) / S
    ).astype(taps.dtype)                                   # (S, L)
    return jnp.einsum("sl,lnbu->snbu", phase, taps)


@dataclasses.dataclass
class WidebandEnsemble:
    """Per-subcarrier ensembles; every array carries a leading S axis."""

    h_beam: jax.Array   # (S, n, B, U) beamspace channels
    w_beam: jax.Array   # (S, n, U, B) LMMSE matrices
    y_beam: jax.Array   # (S, n, B) received vectors
    s: jax.Array        # (S, n, U) transmitted symbols
    bits: jax.Array     # (S, n, U, 4)
    n0: float

    @property
    def S(self) -> int:
        return self.h_beam.shape[0]


def make_wideband_ensemble(
    key, cfg: ChannelConfig, ofdm: OFDMConfig, n: int, snr_db: float,
) -> WidebandEnsemble:
    """S-subcarrier extension of `sim.make_ensemble` (beamspace domain)."""
    kh, ks, kn = jax.random.split(key, 3)
    h = generate_wideband_channels(kh, cfg, ofdm, n)       # (S, n, B, U)
    n0 = float(10.0 ** (-snr_db / 10.0))
    s, bits = qam16_mod(ks, (ofdm.S, n, cfg.U))
    noise = awgn(kn, (ofdm.S, n, cfg.B), n0)
    y = jnp.einsum("snbu,snu->snb", h, s) + noise
    hb = to_beamspace(h, axis=-2)
    yb = to_beamspace(y, axis=-1)
    wb = lmmse_matrix(hb, n0)
    return WidebandEnsemble(hb, wb, yb, s, bits, n0)


class WidebandCalibrator:
    """Cached per-subcarrier calibration / VP-parameter selection.

    Calibration is a serving-time fixed cost: AGC gains (and, when
    requested, the Sec. II-D exponent-list search) depend only on the
    subcarrier's signal statistics, not on the symbol stream, so they are
    computed once per subcarrier and reused across frames.  The cache key
    is the subcarrier index; `specs_for` vectorizes over the whole band.
    """

    def __init__(self, base_spec: EqualizerSpec):
        assert base_spec.is_vp, "wideband path is the B-VP design"
        self.base_spec = base_spec
        self._spec_cache: Dict[tuple, EqualizerSpec] = {}
        self._vp_cache: Dict[Tuple[int, int, int], VPFormat] = {}

    @staticmethod
    def _fingerprint(x) -> tuple:
        """Cheap content stamp so a DIFFERENT ensemble never hits a stale
        cache entry: shape plus a few leading values (deterministic for a
        given ensemble, negligible next to the calibration itself)."""
        head = np.asarray(jnp.ravel(x)[:4])
        return (x.shape, head.tobytes())

    def spec_for(self, s_idx: int, w_s, y_s) -> EqualizerSpec:
        """AGC-calibrated spec for one subcarrier (cached).

        The cache key includes a fingerprint of the operands, so repeated
        frames of the SAME ensemble reuse the gains while a new ensemble
        (different SNR, different channels) recalibrates instead of
        silently inheriting mismatched gains.
        """
        key = (s_idx, self._fingerprint(w_s), self._fingerprint(y_s))
        if key not in self._spec_cache:
            self._spec_cache[key] = calibrate(self.base_spec, w_s, y_s)
        return self._spec_cache[key]

    def specs_for(self, ens: WidebandEnsemble) -> Sequence[EqualizerSpec]:
        return [self.spec_for(s, ens.w_beam[s], ens.y_beam[s])
                for s in range(ens.S)]

    def search_vp_format(
        self, s_idx: int, w_s, M: Optional[int] = None,
        E: Optional[int] = None, max_samples: int = 100_000,
    ) -> VPFormat:
        """Per-subcarrier exponent-list search (Sec. II-D), cached.

        Runs `param_search.search_exponent_list` on the subcarrier's
        normalized W-plane samples against the base spec's FXP grid.
        """
        M = self.base_spec.w_vp.M if M is None else M
        E = self.base_spec.w_vp.E if E is None else E
        key = (s_idx, M, E)
        if key not in self._vp_cache:
            samples = np.asarray(jnp.real(w_s)).ravel()[:max_samples]
            amax = np.abs(samples).max()
            samples = samples / max(amax, 1e-30)
            fmt, _ = param_search.search_exponent_list(
                samples, self.base_spec.w_fxp, M=M, E=E)
            self._vp_cache[key] = fmt
        return self._vp_cache[key]

    @property
    def cache_sizes(self) -> Tuple[int, int]:
        return len(self._spec_cache), len(self._vp_cache)


def _stack_operands(specs: Sequence[EqualizerSpec], w, y):
    """Scale per-subcarrier and stack into batched-kernel operands.

    w (S, n, U, B), y (S, n, B) -> a (S, n, 2U, B), b (S, n, B, 2) floats
    plus the per-subcarrier gain products (S,) to divide back out.
    Packing itself is `mvm_engine.stack_complex_operands` — one source of
    truth for the 4-RM layout across narrowband and wideband paths.
    """
    gw = jnp.asarray([sp.w_gain for sp in specs], jnp.float32)
    gy = jnp.asarray([sp.y_gain for sp in specs], jnp.float32)
    a, b = stack_complex_operands(w, y, gw, gy)
    return a, b, gw * gy


def equalize_wideband(
    specs: Sequence[EqualizerSpec],
    w: jax.Array,            # (S, n, U, B) complex
    y: jax.Array,            # (S, n, B) complex
    how: str = "flat",
    interpret: Optional[bool] = None,
    fused: Optional[bool] = None,
    mesh=None,
    blocks: Optional[Tuple[int, int, int]] = None,
) -> jax.Array:
    """s_hat (S, n, U) through the batched VP kernel, whole band at once.

    `specs` holds one AGC-calibrated B-VP spec per subcarrier (see
    `WidebandCalibrator`); all must share the same static formats — only
    the gains may differ per subcarrier (gains are applied outside the
    quantizer, so they fold into the operands).

    how="flat": fold (S, n) into one leading batch dim — ONE batched
        kernel launch of S·n tile programs (the serving path).
    how="vmap": `jax.vmap` of the per-subcarrier batch over S (the
        autobatching path; identical numerics).
    how="shard_map": shard the subcarrier axis over `mesh`'s "sc" axis
        via `parallel.sharding.shard_over_subcarriers`, each device
        running the flat path on its slab (requires S % mesh size == 0).

    `blocks=None` defers the kernel tiling to `kernels.autotune`
    (persisted tuned entry when one exists, else the shape-clamped
    heuristic — the MVM tile never pads beyond the (2U, B) x (B, 2)
    operands).
    """
    S, n, U, B = w.shape
    if len(specs) != S:
        raise ValueError(f"need one spec per subcarrier: {len(specs)} != {S}")
    fxp_w, vp_w = specs[0].w_fxp, specs[0].w_vp
    fxp_y, vp_y = specs[0].y_fxp, specs[0].y_vp
    for sp in specs:
        if (sp.w_fxp, sp.w_vp, sp.y_fxp, sp.y_vp) != (
                fxp_w, vp_w, fxp_y, vp_y):
            raise ValueError(
                "wideband batch requires one static format across the band "
                "(only AGC gains may vary per subcarrier)")

    # Named scopes `operands` and `combine` label the compiled step's
    # operations around the kernel (metadata only).
    with jax.named_scope("operands"):
        a, b, g = _stack_operands(specs, w, y)

    def _flat(a_f, b_f):
        S_f = a_f.shape[0]
        with jax.named_scope("operands"):
            a_g = a_f.reshape(S_f * n, 2 * U, B)
            b_g = b_f.reshape(S_f * n, B, 2)
        out = batched_complex_mvm(
            a_g, b_g, fxp_w, vp_w, fxp_y, vp_y, interpret=interpret,
            fused=fused, blocks=blocks)
        return out.reshape(S_f, n, 2 * U, 2)

    if how == "flat":
        out = _flat(a, b)
    elif how == "vmap":
        out = jax.vmap(
            lambda a_s, b_s: batched_complex_mvm(
                a_s, b_s, fxp_w, vp_w, fxp_y, vp_y,
                interpret=interpret, fused=fused, blocks=blocks))(a, b)
    elif how == "shard_map":
        from repro.parallel.sharding import shard_over_subcarriers
        out = shard_over_subcarriers(_flat, mesh=mesh, n_subcarriers=S)(a, b)
    else:
        raise ValueError(
            f"unknown how {how!r} (want 'flat', 'vmap' or 'shard_map')")

    with jax.named_scope("combine"):
        return combine_products(out, g)


def wideband_nmse(s_hat, s_true) -> float:
    """Band-averaged NMSE of the equalized symbols."""
    num = float(jnp.mean(jnp.abs(s_hat - s_true) ** 2))
    den = float(jnp.mean(jnp.abs(s_true) ** 2))
    return num / den


def wideband_ber(s_hat, bits) -> float:
    """Hard-decision BER over the whole band."""
    from .sim import qam16_demod_hard

    got = qam16_demod_hard(s_hat)
    return float(jnp.mean(got != bits))
