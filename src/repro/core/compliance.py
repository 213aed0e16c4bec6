"""Grid compliance checks (paper §3): ramp rate and frequency content.

The grid operator supplies a spec (beta, alpha, f_c):

  * |dP/dt| <= beta            for all t      (P normalized to rated power)
  * S(f)    <= alpha           for all f >= f_c

where S(f) is the one-sided normalized DFT magnitude of the power trace —
scaled so S(0) is the trace mean and each bin is interpretable as the
fraction of rated power oscillating at that frequency (paper Fig. 3b shows
S(1/22 Hz) ~= 0.1 for the testbench trace).

Two interfaces:

  * ``check`` — whole-trace oracle (forward-difference ramp + windowed FFT).
  * **Streaming observers** — constant-size state folded chunk-by-chunk
    inside the conditioning engines, so an unbounded campus stream reports
    compliance online without materializing the trace: ``RampObserver``
    carries the last sample across chunk boundaries (a per-chunk
    ``jnp.diff`` silently drops the boundary ramp — the classic streaming
    blind spot), and ``SpectrumObserver`` runs a Goertzel bank over the
    operator's spec lines ``f >= f_c`` as per-chunk second-order
    recurrences folded with exact integer bin-phase rotations (grid
    operators watch specific spectral lines continuously; see "Wide-Area
    Power System Oscillations from Large-Scale AI Workloads").
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import pytree_dataclass


@pytree_dataclass
class GridSpec:
    beta: jax.Array  # max ramp rate [fraction of rated power / s]
    alpha: jax.Array  # spectral cap above f_c
    f_c: jax.Array  # cutoff frequency [Hz]

    @staticmethod
    def create(beta: float = 0.1, alpha: float = 1e-4, f_c: float = 2.0) -> "GridSpec":
        f = lambda v: jnp.asarray(v, jnp.float32)
        return GridSpec(beta=f(beta), alpha=f(alpha), f_c=f(f_c))


def ramp_rate(power: jax.Array, dt: float) -> jax.Array:
    """dP/dt via forward differences; shape (T-1, ...)."""
    return jnp.diff(power, axis=0) / dt


def max_abs_ramp(power: jax.Array, dt: float) -> jax.Array:
    return jnp.max(jnp.abs(ramp_rate(power, dt)), axis=0)


def normalized_spectrum(
    power: jax.Array, dt: float, *, window: str | None = "hann"
) -> tuple[jax.Array, jax.Array]:
    """One-sided normalized magnitude spectrum.

    Returns (freqs [Hz], S) with S[0] ~= mean(power) and interior bins
    scaled so that a sinusoid of amplitude A (fraction of rated power)
    produces S = A at its frequency.

    A Hann window (coherent-gain corrected) is applied by default: grid
    operators estimate spectra over finite measurement windows, and an
    unwindowed DFT of a non-periodic trace leaks its end-discontinuity
    across all bins (~|p(T)-p(0)|/(pi*k)), which would mis-report broadband
    violations that no PSD estimate would show.  ``window=None`` gives the
    raw DFT.
    """
    n = power.shape[0]
    if window == "hann":
        w = 0.5 - 0.5 * jnp.cos(2.0 * jnp.pi * jnp.arange(n) / n)
    elif window is None:
        w = jnp.ones((n,), power.dtype)
    else:
        raise ValueError(f"unknown window {window!r}")
    coherent_gain = jnp.mean(w)
    wshape = (-1,) + (1,) * (power.ndim - 1)
    spec = jnp.abs(jnp.fft.rfft(power * w.reshape(wshape), axis=0)) / (n * coherent_gain)
    # Double interior bins (one-sided); DC and possible Nyquist stay single.
    scale = jnp.ones((spec.shape[0],), power.dtype) * 2.0
    scale = scale.at[0].set(1.0)
    if n % 2 == 0:
        scale = scale.at[-1].set(1.0)
    spec = spec * scale.reshape(wshape)
    freqs = jnp.fft.rfftfreq(n, d=dt)
    return freqs, spec


class ComplianceReport(NamedTuple):
    max_ramp: jax.Array
    ramp_ok: jax.Array
    worst_high_freq_mag: jax.Array
    spectrum_ok: jax.Array
    ok: jax.Array
    # Wide-area oscillation-mode verdicts (grid-region POI reports only;
    # trailing defaults keep every existing constructor and unpack site
    # working).  ``mode_mags``/``mode_ok`` are (B,) per-band arrays aligned
    # with the detector's band table; ``modes_ok`` is the all-bands verdict
    # and is already folded into ``ok`` when present.  None = not tracked.
    mode_mags: jax.Array | None = None
    mode_ok: jax.Array | None = None
    modes_ok: jax.Array | None = None


def with_mode_verdicts(
    report: ComplianceReport, mode_mags: jax.Array, mode_ok: jax.Array
) -> ComplianceReport:
    """Fold per-band oscillation-mode verdicts into a report.

    ``ok`` becomes the conjunction of the ramp, spectrum, and all-bands
    mode verdicts — a POI that rings a wide-area mode band is non-compliant
    even when its ramp and high-frequency lines pass.
    """
    modes_ok = jnp.all(mode_ok)
    return report._replace(
        mode_mags=mode_mags,
        mode_ok=mode_ok,
        modes_ok=modes_ok,
        ok=report.ok & modes_ok,
    )


def check(power: jax.Array, dt: float, spec: GridSpec) -> ComplianceReport:
    """Full compliance check of a normalized power trace (T,) or (T, racks)."""
    mr = max_abs_ramp(power, dt)
    ramp_ok = mr <= spec.beta

    freqs, s = normalized_spectrum(power, dt)
    above = freqs >= spec.f_c
    shape = (-1,) + (1,) * (power.ndim - 1)
    masked = jnp.where(above.reshape(shape), s, 0.0)
    worst = jnp.max(masked, axis=0)
    spectrum_ok = worst <= spec.alpha

    return ComplianceReport(
        max_ramp=mr,
        ramp_ok=ramp_ok,
        worst_high_freq_mag=worst,
        spectrum_ok=spectrum_ok,
        ok=ramp_ok & spectrum_ok,
    )


def violation_fraction(power: jax.Array, dt: float, spec: GridSpec) -> jax.Array:
    """Fraction of time steps whose local ramp exceeds beta (diagnostics)."""
    r = jnp.abs(ramp_rate(power, dt))
    return jnp.mean((r > spec.beta).astype(jnp.float32), axis=0)


# ------------------------------------------------------- streaming observers


class RampObserver(NamedTuple):
    """Cross-chunk running max-ramp: carries the last sample seen so the
    boundary difference between consecutive chunks is never dropped."""

    last: jax.Array  # last sample of the previous chunk
    n: jax.Array  # int32 samples seen
    max_ramp: jax.Array  # running max |dP/dt|


def ramp_observer_init(batch_shape: tuple[int, ...] = ()) -> RampObserver:
    return RampObserver(
        last=jnp.zeros(batch_shape, jnp.float32),
        n=jnp.zeros((), jnp.int32),
        max_ramp=jnp.zeros(batch_shape, jnp.float32),
    )


def ramp_observer_update(
    obs: RampObserver, chunk: jax.Array, dt: float
) -> RampObserver:
    """Fold one (T, ...) chunk.  The first chunk contributes T-1 diffs (the
    carried "previous sample" is seeded with the chunk's own first sample,
    adding an exact zero diff), every later chunk contributes T including
    the boundary — so the running max equals the whole-trace
    ``max_abs_ramp`` bit-for-bit.
    """
    prev = jnp.where(obs.n > 0, obs.last, chunk[0])
    ext = jnp.concatenate([prev[None], chunk], axis=0)
    mr = jnp.max(jnp.abs(jnp.diff(ext, axis=0)), axis=0) / dt
    return RampObserver(
        last=chunk[-1],
        n=obs.n + jnp.int32(chunk.shape[0]),
        max_ramp=jnp.maximum(obs.max_ramp, mr),
    )


@dataclasses.dataclass(frozen=True)
class SpectrumBank:
    """Static configuration of a Goertzel line bank (hashable: rides in jit
    closures and engine cache keys, not in the traced pytree).

    ``bins`` are integer line indices on a length-``modulus`` DFT grid:
    line frequency = ``bin / (modulus * dt)``.  For whole-trace-equivalent
    monitoring set ``modulus = n_total`` and ``window="hann"`` — every line
    is then a bin of the length-``n_total`` DFT and the finalized
    magnitudes match ``normalized_spectrum`` at those bins.  For open-ended
    online monitoring (total length unknown) use ``window=None`` with any
    modulus: lines are fixed operator frequencies and magnitudes normalize
    by the samples seen so far.
    """

    bins: tuple[int, ...]
    modulus: int
    dt: float
    window: str | None = "hann"

    @property
    def freqs(self) -> np.ndarray:
        return np.asarray(self.bins, np.float64) / (self.modulus * self.dt)


def spec_lines(
    n_total: int, dt: float, f_c: float, n_lines: int = 48
) -> tuple[int, ...]:
    """Log-spaced DFT bins of a length-``n_total`` trace covering
    [f_c, Nyquist] — the operator's monitored spec lines."""
    k_lo = max(int(np.ceil(f_c * n_total * dt)), 1)
    k_hi = n_total // 2
    if k_lo > k_hi:
        return ()
    ks = np.round(
        np.logspace(np.log10(k_lo), np.log10(max(k_hi, k_lo)), max(n_lines, 1))
    ).astype(np.int64)
    return tuple(int(k) for k in np.unique(ks))


def make_bank(
    n_total: int, dt: float, f_c: float, *, n_lines: int = 48
) -> SpectrumBank:
    """Whole-trace-equivalent bank: Hann window, lines on the trace's bins."""
    return SpectrumBank(
        bins=spec_lines(n_total, dt, f_c, n_lines),
        modulus=int(n_total),
        dt=float(dt),
        window="hann",
    )


def make_online_bank(
    dt: float, f_c: float, *, n_lines: int = 24, modulus: int = 1 << 15
) -> SpectrumBank:
    """Open-ended bank (total length unknown): rectangular window, lines on
    a fixed length-``modulus`` frequency grid."""
    return SpectrumBank(
        bins=spec_lines(modulus, dt, f_c, n_lines),
        modulus=int(modulus),
        dt=float(dt),
        window=None,
    )


class SpectrumObserver(NamedTuple):
    """Running Goertzel-bank state: complex line accumulators + the exact
    integer bin phase of the next sample (kept mod ``modulus`` so the
    cross-chunk rotation never loses precision, however long the stream)."""

    acc_re: jax.Array  # (L,)
    acc_im: jax.Array  # (L,)
    phase: jax.Array  # (L,) int32: (bin * samples_seen) mod modulus
    n: jax.Array  # int32 samples seen


def spectrum_observer_init(bank: SpectrumBank) -> SpectrumObserver:
    l = len(bank.bins)
    return SpectrumObserver(
        acc_re=jnp.zeros((l,), jnp.float32),
        acc_im=jnp.zeros((l,), jnp.float32),
        phase=jnp.zeros((l,), jnp.int32),
        n=jnp.zeros((), jnp.int32),
    )


def spectrum_observer_update(
    bank: SpectrumBank, obs: SpectrumObserver, chunk: jax.Array
) -> SpectrumObserver:
    """Fold one (T,) chunk: a local Goertzel recurrence per line (float32
    error stays bounded by the chunk length, not the stream length), then
    rotate the local DFT onto the absolute stream position with the exact
    integer bin phase carried in the state."""
    if not bank.bins:
        return SpectrumObserver(
            obs.acc_re, obs.acc_im, obs.phase,
            obs.n + jnp.int32(chunk.shape[0]),
        )
    m = chunk.shape[0]
    mod = bank.modulus
    bins = np.asarray(bank.bins, np.int64)
    omega = (2.0 * np.pi / mod) * bins.astype(np.float64)
    coeff = jnp.asarray(2.0 * np.cos(omega), jnp.float32)  # (L,)

    if bank.window == "hann":
        # Hann value at the *absolute* index: exact integer phase mod N.
        wp = jnp.mod(obs.n + jnp.arange(m, dtype=jnp.int32), mod)
        w = 0.5 - 0.5 * jnp.cos(
            wp.astype(jnp.float32) * jnp.float32(2.0 * np.pi / mod)
        )
        x = chunk * w
    elif bank.window is None:
        x = chunk
    else:
        raise ValueError(f"unknown window {bank.window!r}")

    def body(carry, xv):
        s1, s2 = carry
        s0 = xv + coeff * s1 - s2
        return (s0, s1), None

    zeros = jnp.zeros((len(bank.bins),), jnp.float32)
    (s1, s2), _ = jax.lax.scan(body, (zeros, zeros), x.astype(jnp.float32))

    # Local block DFT: X_b = (s_{M-1} - s_{M-2} e^{-iw}) e^{-iw(M-1)}.
    e_re = jnp.asarray(np.cos(omega), jnp.float32)
    e_im = jnp.asarray(-np.sin(omega), jnp.float32)
    xb_re = s1 - (s2 * e_re)
    xb_im = -(s2 * e_im)
    tail = np.exp(-1j * omega * (m - 1))
    t_re = jnp.asarray(tail.real, jnp.float32)
    t_im = jnp.asarray(tail.imag, jnp.float32)
    xb_re, xb_im = xb_re * t_re - xb_im * t_im, xb_re * t_im + xb_im * t_re

    # Rotate onto the absolute position: e^{-2pi i * phase / modulus} with
    # the exact integer phase carried in the observer.
    ang = obs.phase.astype(jnp.float32) * jnp.float32(2.0 * np.pi / mod)
    r_re, r_im = jnp.cos(ang), -jnp.sin(ang)
    acc_re = obs.acc_re + (xb_re * r_re - xb_im * r_im)
    acc_im = obs.acc_im + (xb_re * r_im + xb_im * r_re)

    # Advance the bin phase by m samples, exactly (int32 mod arithmetic:
    # both operands already < modulus, so the product path is avoided).
    adv = jnp.asarray((bins * (m % mod)) % mod, jnp.int32)
    phase = jnp.mod(obs.phase + adv, mod)
    return SpectrumObserver(
        acc_re=acc_re, acc_im=acc_im, phase=phase,
        n=obs.n + jnp.int32(m),
    )


def hann_norm(n: int) -> jax.Array:
    """``n`` times the mean of the length-``n`` Hann window: the
    coherent-gain normalization of a Hann bank's line magnitudes."""
    w = 0.5 - 0.5 * jnp.cos(2.0 * jnp.pi * jnp.arange(n) / n)
    return n * jnp.mean(w)


def spectrum_observer_finalize(
    bank: SpectrumBank, obs: SpectrumObserver, norm: jax.Array | None = None
) -> tuple[np.ndarray, jax.Array]:
    """(freqs [Hz], S) at the bank lines, normalized exactly like
    ``normalized_spectrum`` (coherent-gain corrected, one-sided doubling).
    Hann banks normalize by the configured total length (``norm``, when
    given, is its ``hann_norm`` computed beforehand); rectangular (online)
    banks by the samples seen so far."""
    if not bank.bins:
        return np.zeros((0,)), jnp.zeros((0,), jnp.float32)
    mag = jnp.sqrt(obs.acc_re**2 + obs.acc_im**2)
    if bank.window == "hann":
        norm = hann_norm(bank.modulus) if norm is None else norm
    else:
        norm = jnp.maximum(obs.n.astype(jnp.float32), 1.0)
    bins = np.asarray(bank.bins, np.int64)
    # One-sided doubling, except DC and the Nyquist line (bin modulus/2 of
    # an even grid is its own conjugate — single-sided in any real DFT).
    nyq = bank.modulus % 2 == 0
    scale = np.where((bins > 0) & ~(nyq & (bins == bank.modulus // 2)), 2.0, 1.0)
    return bank.freqs, mag * jnp.asarray(scale, jnp.float32) / norm


def report_from_observers(
    spec: GridSpec,
    ramp: RampObserver,
    bank: SpectrumBank,
    sob: SpectrumObserver,
    norm: jax.Array | None = None,
) -> ComplianceReport:
    """ComplianceReport from streaming state: the ramp bound is exact; the
    spectral bound is evaluated at the bank's monitored lines (all >= f_c
    by construction) rather than every DFT bin.  ``norm`` is a Hann bank's
    ``hann_norm``, computed beforehand by a caller that compiles the
    report (traced inside a program, the window's ``cos`` and mean may be
    folded at compile time to another ulp)."""
    _, s = spectrum_observer_finalize(bank, sob, norm)
    worst = jnp.max(s, initial=0.0)
    ramp_ok = ramp.max_ramp <= spec.beta
    spectrum_ok = worst <= spec.alpha
    return ComplianceReport(
        max_ramp=ramp.max_ramp,
        ramp_ok=ramp_ok,
        worst_high_freq_mag=worst,
        spectrum_ok=spectrum_ok,
        ok=ramp_ok & spectrum_ok,
    )
