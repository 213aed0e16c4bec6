"""Grid-facing reports of one call's campus or POI trace, in float64.

* Ramp: the largest |p[i+1] - p[i]| / dt inside the call.
* Spectrum: the one-sided Hann-windowed DFT magnitude, scaled so that a
  sinusoid of amplitude A reads A, at 48 log-spaced bins of the call's own
  length between f_c and Nyquist; the report is the largest.
* POI swing (regions): M df/dt = -(rf dP + D f) by forward Euler on the
  deviation of the POI trace from its mean, in Hz; and the wide-area mode
  bands, every DFT bin inside each band (evenly strided to at most 96),
  with the band's largest magnitude against its threshold.

``dtype`` (the control's) computes the trace's products and sums in that
precision on the device instead of float64 on the host.
"""
from __future__ import annotations

import numpy as np


def _lower(x, dtype):
    """``x`` rounded to ``dtype`` and back (None keeps float64)."""
    if dtype is None:
        return np.asarray(x, np.float64)
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x, dtype).astype(jnp.float32), np.float64)


def max_ramp(x: np.ndarray, dt: float, dtype=None) -> float:
    x = _lower(x, dtype)
    d = _lower(np.diff(x), dtype)
    return float(np.max(np.abs(d))) / dt if x.size > 1 else 0.0


def spec_bins(n: int, dt: float, f_c: float, n_lines: int = 48) -> np.ndarray:
    k_lo = max(int(np.ceil(f_c * n * dt)), 1)
    k_hi = n // 2
    if k_lo > k_hi:
        return np.zeros((0,), np.int64)
    ks = np.round(np.logspace(np.log10(k_lo), np.log10(k_hi), n_lines)).astype(np.int64)
    return np.unique(ks)


def dft_mags(x: np.ndarray, bins: np.ndarray, dtype=None) -> np.ndarray:
    """Normalised Hann-windowed DFT magnitudes of ``x`` at ``bins``."""
    x = np.asarray(x, np.float64)
    n = x.shape[0]
    if bins.size == 0:
        return np.zeros((0,))
    i = np.arange(n)
    w = 0.5 - 0.5 * np.cos(2.0 * np.pi * i / n)
    ang = 2.0 * np.pi * np.outer(bins, i) / n
    xw = _lower(_lower(x, dtype) * _lower(w, dtype), dtype)
    if dtype is None:
        re, im = np.cos(ang) @ xw, np.sin(ang) @ xw
    else:
        import jax.numpy as jnp

        mm = lambda a: np.asarray(
            jnp.matmul(jnp.asarray(a, dtype), jnp.asarray(xw, dtype)).astype(jnp.float32),
            np.float64)
        re, im = mm(np.cos(ang)), mm(np.sin(ang))
    mag = np.hypot(re, im)
    scale = np.where((bins > 0) & ~((n % 2 == 0) & (bins == n // 2)), 2.0, 1.0)
    return mag * scale / (n * w.mean())


def worst_line(x: np.ndarray, dt: float, f_c: float, dtype=None) -> float:
    mags = dft_mags(x, spec_bins(len(x), dt, f_c), dtype)
    return float(mags.max()) if mags.size else 0.0


def poi_swing(poi: np.ndarray, dt: float, cfg: dict, dtype=None) -> np.ndarray:
    dp = _lower(poi, dtype)
    dp = _lower(cfg["region_fraction"] * _lower(dp - dp.mean(), dtype), dtype)
    a, damp = dt / cfg["inertia_s"], cfg["damping"]
    f = 0.0
    out = np.empty_like(dp)
    for i, d in enumerate(dp):
        f = f + a * (-d - damp * f)
        out[i] = f
    return _lower(out, dtype) * cfg["f0_hz"]


def mode_bins(n: int, dt: float, bands, max_lines: int = 96) -> np.ndarray:
    bins = set()
    for _, lo_hz, hi_hz, _ in bands:
        k_lo = max(int(np.ceil(lo_hz * n * dt)), 1)
        k_hi = min(int(np.floor(hi_hz * n * dt)), n // 2)
        if k_hi < k_lo:
            continue
        ks = np.arange(k_lo, k_hi + 1)
        if ks.size > max_lines:
            ks = np.unique(np.round(np.linspace(k_lo, k_hi, max_lines)).astype(np.int64))
        bins.update(int(k) for k in ks)
    return np.asarray(sorted(bins), np.int64)


def mode_mags(poi: np.ndarray, dt: float, bands, dtype=None) -> np.ndarray:
    """The largest monitored magnitude inside each band (0 where the call
    is too short to resolve the band); a band passes at or under its
    threshold."""
    n = len(poi)
    bins = mode_bins(n, dt, bands)
    mags = dft_mags(poi, bins, dtype)
    freqs = bins / (n * dt)
    out = []
    for _, lo_hz, hi_hz, _ in bands:
        sel = (freqs >= lo_hz) & (freqs < hi_hz)
        out.append(float(mags[sel].max()) if np.any(sel) else 0.0)
    return np.asarray(out)
