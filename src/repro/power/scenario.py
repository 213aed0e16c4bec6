"""Declarative scenario engine: workload events -> batched, chunk-renderable traces.

The legacy trace layer (`repro.power.trace`) synthesizes one homogeneous
square-wave per call with host-side Python branches, and fleet heterogeneity
is bolted on by rolling copies of that one trace.  This module replaces the
construction with *data*: a scenario is a small struct-of-arrays IR —

  * ``WorkloadParams``: the parametric per-rack workload (warmup ramp,
    iteration compute/communicate wave, periodic checkpoint dips, job
    start/stop envelope, fault window, diurnal inference envelope, noise)
    with every knob a float32 leaf, so a heterogeneous fleet is just a
    ``WorkloadParams`` whose leaves carry a trailing rack axis ``(R,)``;
  * an optional explicit segment table (``seg_bounds``/``seg_powers``) for
    compiled phase timelines (`repro.power.phases`), piecewise-constant
    power looked up by sample index.

``render(scenario, t0, n)`` is a pure jit-ed function of the *absolute*
sample index: every output sample depends only on its own index (the edge
smoothing is an explicit zero-padded window mean with a fixed reduction
order), so chunked rendering is **bit-identical** to whole-trace rendering
and the fleet's scanned engine renders each chunk on-device inside its
scan — campus-scale traces are never materialized as (T, R).

Workload parameters for the assigned model architectures are derived from
their step cost (``workload_from_model`` / ``scenario_from_model``), and
``mixed_campus`` builds the paper's heterogeneous-campus evaluation: many
models, staggered job starts/stops, an inference-diurnal block, and a
mid-trace fault cascade.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import pytree_dataclass, static_field

# "never happens" sentinel for event times; float32-representable and far
# beyond any trace, so `t >= NEVER` comparisons are exactly False in-band.
NEVER = 1e30


@pytree_dataclass
class WorkloadParams:
    """Parametric per-rack workload (struct-of-arrays).

    Every field is a float32 leaf of shape ``()`` (one rack) or ``(R,)``
    (per-rack batch); heterogeneous fleets fall out of broadcasting the
    time axis against the trailing rack axis.  Defaults mirror
    ``trace.TestbenchSpec`` (Choukse et al. Fig. 1 structure).
    """

    # Iteration wave: compute plateau with a comm window at the cycle end.
    iteration_period_s: jax.Array
    comm_fraction: jax.Array
    p_compute: jax.Array
    p_comm: jax.Array
    # Periodic deep dips (checkpoint stalls).
    dip_period_s: jax.Array
    dip_duration_s: jax.Array
    p_dip: jax.Array
    # Job envelope: idle -> warmup ramp at t_start, drop to idle at t_end.
    warmup_s: jax.Array
    p_idle: jax.Array
    t_start_s: jax.Array
    t_end_s: jax.Array
    # Fault window: near-instant drop, bypasses edge smoothing (Fig. 13).
    fault_at_s: jax.Array
    fault_duration_s: jax.Array
    p_fault: jax.Array
    # Diurnal inference envelope: amp=0 disables (exact no-op); amp in
    # (0, 1] swings the load between full and (1-amp) of its workload
    # excursion over p_idle, with period diurnal_period_s.
    diurnal_period_s: jax.Array
    diurnal_amp: jax.Array
    diurnal_phase_s: jax.Array
    # Per-rack output scale and measurement-noise level.
    scale: jax.Array
    noise_std: jax.Array


def _concrete(x) -> np.ndarray | None:
    """Host view of a value for construction-time validation; None if the
    value is a tracer (validation is skipped inside jit — the builders are
    host-side constructors in every supported path)."""
    try:
        return np.asarray(x)
    except Exception:
        return None


def _validate_workload(w: WorkloadParams) -> WorkloadParams:
    fd = _concrete(w.fault_duration_s)
    if fd is not None and np.any(fd < 0.0):
        raise ValueError(
            f"fault_duration_s must be >= 0, got {fd} — a negative window "
            "would silently render as no fault at all"
        )
    fa = _concrete(w.fault_at_s)
    if fa is not None and np.any(fa < 0.0):
        raise ValueError(
            f"fault_at_s must be >= 0 (or NEVER to disable), got {fa}"
        )
    return w


def workload(
    *,
    iteration_period_s=22.0,
    comm_fraction=0.114,
    p_compute=0.92,
    p_comm=0.25,
    dip_period_s=110.0,
    dip_duration_s=3.0,
    p_dip=0.15,
    warmup_s=8.0,
    p_idle=0.10,
    t_start_s=0.0,
    t_end_s=NEVER,
    fault_at_s=NEVER,
    fault_duration_s=20.0,
    p_fault=0.02,
    diurnal_period_s=NEVER,
    diurnal_amp=0.0,
    diurnal_phase_s=0.0,
    scale=1.0,
    noise_std=0.01,
) -> WorkloadParams:
    """Build ``WorkloadParams`` from keyword knobs (scalars or (R,) arrays)."""
    as32 = lambda x: jnp.asarray(x, jnp.float32)
    return _validate_workload(WorkloadParams(
        iteration_period_s=as32(iteration_period_s),
        comm_fraction=as32(comm_fraction),
        p_compute=as32(p_compute),
        p_comm=as32(p_comm),
        dip_period_s=as32(dip_period_s),
        dip_duration_s=as32(dip_duration_s),
        p_dip=as32(p_dip),
        warmup_s=as32(warmup_s),
        p_idle=as32(p_idle),
        t_start_s=as32(t_start_s),
        t_end_s=as32(t_end_s),
        fault_at_s=as32(fault_at_s),
        fault_duration_s=as32(fault_duration_s),
        p_fault=as32(p_fault),
        diurnal_period_s=as32(diurnal_period_s),
        diurnal_amp=as32(diurnal_amp),
        diurnal_phase_s=as32(diurnal_phase_s),
        scale=as32(scale),
        noise_std=as32(noise_std),
    ))


def stack_workloads(params_list: list[WorkloadParams]) -> WorkloadParams:
    """Stack per-rack scalar params into one (R,)-batched ``WorkloadParams``."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.broadcast_to(x, ()) for x in xs]), *params_list
    )


@pytree_dataclass
class Scenario:
    """A renderable scenario: parametric workloads and/or a segment table.

    If ``seg_powers`` is present the base waveform is the piecewise-constant
    segment lookup (``seg_bounds`` holds int32 start-sample indices,
    ``seg_bounds[0] == 0``; ``seg_powers`` is ``(K,)`` shared or ``(R, K)``
    per-rack); otherwise it is the parametric ``params`` workload.  Static
    fields (sample rate, length, smoothing width, noise seed) are jit aux
    data, so one compiled ``render`` serves every chunk.
    """

    params: WorkloadParams | None
    seg_bounds: jax.Array | None
    seg_powers: jax.Array | None
    # Noise level for segment-table scenarios (parametric scenarios carry
    # theirs in ``params.noise_std``); None = 0.
    seg_noise_std: jax.Array | None = None
    # Compiled stochastic fault schedule (``power.faults.FaultSchedule``);
    # the rack-power-loss and sensor-dropout channels apply at render time,
    # the ESS-trip channel is consumed by the fleet engines' per-interval
    # availability mask.  None = fault-free.
    faults: object | None = None
    # Optional uint32 scalar XORed into the noise lane hash — a *traced*
    # leaf, so campuses stacked for the sharded grid-region engine (which
    # must share every static field, including ``noise_seed``) can still
    # draw decorrelated measurement noise.  None keeps the legacy stream
    # bit-for-bit (see ``with_noise_salt``).
    noise_salt: jax.Array | None = None
    sample_hz: float = static_field(default=1000.0)
    total_samples: int = static_field(default=0)
    # Edge smoothing window in samples (0/1 = off): steps become linear
    # ramps of ~edge_width*dt, identical to the legacy boxcar convolution.
    edge_width: int = static_field(default=0)
    # Boundary handling for the smoothing window: "zero" (legacy boxcar —
    # samples beyond the trace read as 0, so power decays to ~p/2 across
    # the first/last half-window) or "clamp" (edge replication — no
    # fabricated transient at the trace boundaries).  "zero" keeps every
    # existing trace bitwise; "clamp" is what compliance-bearing campus
    # benches want, since the zero-pad decay is synchronized fleet-wide
    # and reads as a phantom campus-scale power step.
    edge_pad: str = static_field(default="zero")
    # Counter-based noise: sample i draws from fold_in(key(seed), i), so
    # noise is chunk-invariant.  None disables noise entirely.
    noise_seed: int | None = static_field(default=None)

    @property
    def duration_s(self) -> float:
        return self.total_samples / self.sample_hz

    @property
    def dt(self) -> float:
        return 1.0 / self.sample_hz

    @property
    def n_racks(self) -> int | None:
        """Rack batch size, or None for an unbatched (T,) scenario."""
        if self.seg_powers is not None and self.seg_powers.ndim == 2:
            return self.seg_powers.shape[0]
        if self.params is not None:
            for leaf in jax.tree_util.tree_leaves(self.params):
                if jnp.ndim(leaf) == 1:
                    return leaf.shape[0]
        return None


def make_scenario(
    params: WorkloadParams,
    *,
    duration_s: float,
    sample_hz: float,
    edge_time_s: float = 0.25,
    edge_pad: str = "zero",
    noise_seed: int | None = None,
    faults=None,
) -> Scenario:
    """Wrap parametric workloads into a renderable ``Scenario``.

    A scripted ``fault_at_s`` must land inside the trace: a window starting
    at or past ``duration_s`` would silently render as a no-op, so it is
    rejected here (use ``NEVER`` to disable the fault).
    """
    total = int(round(duration_s * sample_hz))
    if edge_pad not in ("zero", "clamp"):
        raise ValueError(
            f"edge_pad must be 'zero' or 'clamp', got {edge_pad!r}"
        )
    fa = _concrete(params.fault_at_s)
    if fa is not None:
        scripted = fa < 0.5 * NEVER
        if np.any(scripted & (fa * sample_hz >= total)):
            bad = np.asarray(fa)[np.asarray(scripted & (fa * sample_hz >= total))]
            raise ValueError(
                f"fault_at_s {np.unique(bad)} is past the scenario end "
                f"({duration_s} s = {total} samples); use NEVER to disable"
            )
    return Scenario(
        params=params,
        seg_bounds=None,
        seg_powers=None,
        faults=faults,
        sample_hz=float(sample_hz),
        total_samples=total,
        edge_width=_edge_width(edge_time_s, sample_hz),
        edge_pad=edge_pad,
        noise_seed=noise_seed,
    )


def attach_faults(
    s: Scenario,
    process_or_schedule,
    *,
    seed: int = 0,
    max_episodes: int | None = None,
) -> Scenario:
    """Return ``s`` with a stochastic fault schedule attached.

    Accepts a ``faults.FaultProcess`` (sampled here against the scenario's
    geometry with counter-based draws) or a pre-built
    ``faults.FaultSchedule`` (rack count must match).  A pre-built
    schedule's episode tables are validated host-side
    (``faults.validate_tables``): the interval-compiled fault path selects
    episode boundaries by rank, which assumes sorted, coalesced,
    sentinel-padded rows — hand-built tables that violate this would
    silently render the wrong availability.
    """
    from repro.power import faults as FLT

    n = s.n_racks or 1
    if isinstance(process_or_schedule, FLT.FaultSchedule):
        sched = process_or_schedule
        FLT.validate_tables(sched)
    else:
        sched = FLT.sample_schedule(
            process_or_schedule, n, s.total_samples, s.sample_hz,
            seed=seed, max_episodes=max_episodes,
        )
    if sched.n_racks != n:
        raise ValueError(
            f"fault schedule covers {sched.n_racks} racks but the scenario "
            f"has {n}"
        )
    return s.replace(faults=sched)


def with_noise_salt(s: Scenario, salt: int | jax.Array) -> Scenario:
    """Return ``s`` drawing a decorrelated measurement-noise stream.

    The salt is a *traced* uint32 leaf XORed into the counter hash's lane
    seed, so scenarios that must share every static field (campuses stacked
    for the sharded grid-region engine share one ``noise_seed`` aux datum)
    still get independent noise.  A scenario without noise is returned
    unchanged — salting silence would only force a treedef change.
    """
    if s.noise_seed is None:
        return s
    return s.replace(noise_salt=jnp.asarray(salt, jnp.uint32))


def _edge_width(edge_time_s: float, sample_hz: float) -> int:
    return max(int(round(edge_time_s * sample_hz)), 1) if edge_time_s > 0 else 0


# ------------------------------------------------------------------ rendering


def _floor_mod(x: jax.Array, y: jax.Array) -> jax.Array:
    """Bitwise-exact ``jnp.mod(x, y)`` for ``y > 0`` without libm ``fmod``.

    ``jnp.mod`` lowers to an elementwise ``remainder`` that XLA:CPU serves
    with a scalar libm call — by far the hottest op in ``_parametric_base``
    (the two phase mods were ~74% of the pre-smoothing render).  This
    computes the same value with vectorizable arithmetic:

      k  = trunc(x / y)            # candidate C-style quotient
      r  = x - k*y                 # exact via a Dekker-split product
      k += (r >= y) - (r < 0)      # division rounding puts k off by <= 1
      r  = x - k*y                 # exact C remainder (representable)
      m  = r + y if r < 0 else r   # numpy floor-mod fixup (one rounding)

    The subtraction ``x - k*y`` is exact because the true C remainder is
    representable (the classical fmod invariant) and the split product
    recovers the low bits of ``k*y``; the final fixup performs the same
    single rounding numpy's ``fmod -> m += y`` path does.  Verified
    bitwise against ``jnp.mod`` over 2M values per period covering the
    workload range (negative job-local times, exact multiples, boundary
    neighbours, ``NEVER`` sentinels).
    """
    c = jnp.float32(4097.0)  # 2^12 + 1 Dekker splitter

    def sub_prod(x, k, y):
        ck = c * k
        k_hi = ck - (ck - k)
        k_lo = k - k_hi
        cy = c * y
        y_hi = cy - (cy - y)
        y_lo = y - y_hi
        p_hi = k * y
        p_lo = ((k_hi * y_hi - p_hi) + k_hi * y_lo + k_lo * y_hi) + k_lo * y_lo
        return (x - p_hi) - p_lo

    k = jnp.trunc(x / y)
    r1 = sub_prod(x, k, y)
    k = k + (r1 >= y).astype(x.dtype) - (r1 < 0).astype(x.dtype)
    rc = sub_prod(x, k, y)
    return jnp.where(rc < 0, rc + y, rc)


def _parametric_base(w: WorkloadParams, t: jax.Array, dt: float) -> jax.Array:
    """Per-sample base power at times ``t`` (seconds); pure and elementwise.

    Ordering matches the legacy ``testbench_trace`` exactly (wave -> dips ->
    warmup ramp -> envelope) so that with default start/diurnal/scale the
    pre-smoothing samples are bitwise-identical to the legacy path.
    """
    batched = any(jnp.ndim(x) == 1 for x in jax.tree_util.tree_leaves(w))
    if batched:
        t = t[:, None]
    te = t - w.t_start_s  # job-local time (staggered starts)

    phase = _floor_mod(te, w.iteration_period_s) / w.iteration_period_s
    p = jnp.where(phase >= 1.0 - w.comm_fraction, w.p_comm, w.p_compute)
    # NEVER disables dips entirely (mod(te, NEVER) == te would otherwise
    # fire a spurious dip for the first dip_duration_s of every job).
    in_dip = (_floor_mod(te, w.dip_period_s) < w.dip_duration_s) & (
        w.dip_period_s < 0.5 * NEVER
    )
    p = jnp.where(in_dip, w.p_dip, p)
    ramp = jnp.clip(te / jnp.maximum(w.warmup_s, dt), 0.0, 1.0)
    p = w.p_idle + ramp * (p - w.p_idle)
    # Diurnal inference envelope (amp=0 keeps p bitwise-unchanged).
    period = jnp.maximum(w.diurnal_period_s, dt)
    env = 1.0 - w.diurnal_amp * 0.5 * (
        1.0 - jnp.cos(2.0 * jnp.pi * (t - w.diurnal_phase_s) / period)
    )
    p = jnp.where(w.diurnal_amp > 0.0, w.p_idle + env * (p - w.p_idle), p)
    # Outside the job window the rack idles (termination is abrupt).
    return jnp.where((te < 0.0) | (t >= w.t_end_s), w.p_idle, p)


def _segment_base(s: Scenario, idx: jax.Array) -> jax.Array:
    j = jnp.clip(
        jnp.searchsorted(s.seg_bounds, idx, side="right") - 1,
        0,
        s.seg_bounds.shape[0] - 1,
    )
    if s.seg_powers.ndim == 2:
        return s.seg_powers[:, j].T  # (n, R)
    return s.seg_powers[j]


def _base(s: Scenario, idx: jax.Array) -> jax.Array:
    if s.seg_powers is not None:
        return _segment_base(s, idx)
    return _parametric_base(s.params, idx.astype(jnp.float32) * s.dt, s.dt)


def _window_mean(base: jax.Array, n: int, w: int) -> jax.Array:
    """Mean over the ``w``-sample boxcar via shared dyadic partial sums.

    A window of overlapping boxcars shares its partial sums: one add per
    dyadic level builds ``s_k[i] = sum(base[i:i+k])`` for ``k = 2, 4, ...``
    and the binary digits of ``w`` then stitch each window from
    ``popcount(w)`` slices — ``O(log w)`` full-array adds instead of the
    ``w - 1`` a per-shift reduction pays (w=50 at fleet width: 7 passes vs
    49, about half the render's smoothing time).  Every partial is indexed
    by absolute position and the stitch topology is fixed by ``w`` alone,
    so chunked rendering stays bit-identical to the whole trace — the same
    contract the old fixed-topology pairwise tree provided (the two differ
    by ulp-level reassociation, covered by the legacy-compare tolerance)."""
    levels = {1: base}
    k = 1
    while 2 * k <= w:
        s = levels[k]
        levels[2 * k] = s[:-k] + s[k:]
        k *= 2
    acc, off, rem = None, 0, w
    while rem:
        p = 1 << (rem.bit_length() - 1)
        part = levels[p][off : off + n]
        acc = part if acc is None else acc + part
        off += p
        rem -= p
    return acc / w


def _fmix32(x: jax.Array) -> jax.Array:
    """murmur3's 32-bit avalanche finalizer (full-avalanche integer mix)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _hash_normal(
    seed: int, idx: jax.Array, tail: tuple[int, ...], salt: jax.Array | None = None
) -> jax.Array:
    """Counter-hashed standard-normal measurement noise, pure in the
    absolute sample index.

    ``noise[t, r] = sqrt(2) * erfinv(2 u - 1)`` with the uniform ``u``
    drawn from a murmur3-finalizer hash of ``(seed, t, r)`` — exact normal
    marginals through the inverse CDF, one fused elementwise pass.  The
    per-rack term is hashed once per rack and XORed into the per-sample
    counter, so the hot loop is a single ``_fmix32`` per sample; that
    replaces the previous per-row ``fold_in`` + threefry draw at ~3x less
    render time (threefry's 20-round block cipher is the wrong tool for
    measurement noise — any full-avalanche counter hash gives the same
    chunk-bitwise contract).  ``u`` is centered to ``[2^-25, 1 - 2^-25]``
    so ``erfinv`` never sees ``+/-1``.

    ``salt`` (a traced uint32 scalar) is XORed into the per-rack lane seed
    before the avalanche mix, giving a decorrelated stream per salt value
    at zero extra per-sample cost; ``salt=None`` is bitwise-identical to
    the unsalted path."""
    s = jnp.uint32(seed)
    r = tail[0] if tail else 1
    lane_seed = (
        jnp.arange(r, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
        ^ (s * jnp.uint32(0x85EBCA6B) + jnp.uint32(0x2545F491))
    )
    if salt is not None:
        lane_seed = lane_seed ^ jnp.asarray(salt, jnp.uint32)
    lane = _fmix32(lane_seed)
    h = _fmix32(idx.astype(jnp.uint32)[:, None] ^ lane[None, :])
    u = (h >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0**-24)
    u = u + jnp.float32(2.0**-25)
    z = jnp.float32(np.sqrt(2.0)) * jax.scipy.special.erfinv(2.0 * u - 1.0)
    return z if tail else z[:, 0]


def _render_impl(s: Scenario, t0: jax.Array, n: int) -> jax.Array:
    t0 = jnp.asarray(t0, jnp.int32)
    idx = t0 + jnp.arange(n, dtype=jnp.int32)
    w = s.edge_width
    if w > 1:
        # Zero-padded window mean over [i-(w-1-c), i+c], c=(w-1)//2 — the
        # exact window of jnp.convolve(p, ones(w)/w, mode="same").
        c = (w - 1) // 2
        lo = w - 1 - c
        eidx = (t0 - lo) + jnp.arange(n + w - 1, dtype=jnp.int32)
        if s.edge_pad == "clamp":
            # Edge replication: the window reads the first/last sample
            # instead of zeros, so the trace boundaries carry no phantom
            # decay.  Still pure in the absolute index -> chunk-bitwise.
            base = _base(s, jnp.clip(eidx, 0, s.total_samples - 1))
        else:
            base = _base(s, eidx)
            valid = (eidx >= 0) & (eidx < s.total_samples)
            base = jnp.where(
                valid if base.ndim == 1 else valid[:, None], base, 0.0
            )
        p = _window_mean(base, n, w)
    else:
        p = _base(s, idx)

    wp = s.params
    if wp is not None:
        # Fault window bypasses edge smoothing: the near-instant drop is the
        # point (paper Fig. 13).
        t = idx.astype(jnp.float32) * s.dt
        tb = t[:, None] if p.ndim == 2 else t
        in_fault = (tb >= wp.fault_at_s) & (tb < wp.fault_at_s + wp.fault_duration_s)
        p = jnp.where(in_fault, wp.p_fault, p)

    if s.faults is not None:
        # Stochastic rack power loss: the collapse/recovery is linearised
        # over the scenario's edge window (PSU bulk caps + staggered server
        # shutdown — see faults.fault_weight), still pure in the absolute
        # sample index, so chunked rendering stays bit-identical.
        from repro.power import faults as _flt

        wgt = _flt.fault_weight(s.faults, t0, n, max(w, 1))  # (n, R)
        pf = s.faults.p_fault
        if p.ndim == 1:
            wgt, pf = wgt[:, 0], pf[0]
        p = p + wgt * (pf - p)

    if s.noise_seed is not None:
        noise = _hash_normal(s.noise_seed, idx, p.shape[1:], s.noise_salt)
        if wp is not None:
            std = wp.noise_std
        else:
            std = s.seg_noise_std if s.seg_noise_std is not None else 0.0
        p = jnp.clip(p + std * noise, 0.0, 1.0)

    if wp is not None:
        p = p * wp.scale

    if s.faults is not None:
        # Sensor dropout is a *measurement* fault, so it lands last: the
        # telemetry consumer sees NaN where the sensor went dark.  The fleet
        # engines bridge these with a last-good-sample hold before any state
        # update, so NaN never enters the conditioning scan.
        from repro.power import faults as _flt

        dead = _flt.sensor_down(s.faults, t0, n)
        if p.ndim == 1:
            dead = dead[:, 0]
        p = jnp.where(dead, jnp.nan, p)
    return p.astype(jnp.float32)


render = jax.jit(_render_impl, static_argnames="n")
render.__doc__ = """Render ``n`` samples starting at absolute sample ``t0``.

Returns ``(n,)`` for an unbatched scenario or ``(n, R)`` for a per-rack
batch.  Pure in the absolute index: ``render(s, 0, T)`` equals the
concatenation of any chunking ``render(s, t0, n)`` bit-for-bit, so a
stream can render chunk by chunk.
"""


def _render_padded_impl(s: Scenario, t0: jax.Array, n: int) -> jax.Array:
    tr = _render_impl(s, t0, n)
    t0 = jnp.asarray(t0, jnp.int32)
    idx = t0 + jnp.arange(n, dtype=jnp.int32)
    # Position of the last in-range sample within this chunk; holding it for
    # every out-of-range row is a zero-order hold of the trace's last sample
    # (a repeat of tr[-1:]).
    last = jnp.clip(jnp.int32(s.total_samples - 1) - t0, 0, n - 1)
    hold = jax.lax.dynamic_index_in_dim(tr, last, axis=0, keepdims=True)
    valid = idx < s.total_samples
    return jnp.where(valid if tr.ndim == 1 else valid[:, None], tr, hold)


render_padded = jax.jit(_render_padded_impl, static_argnames="n")
render_padded.__doc__ = """``render`` with ZOH padding past the scenario end.

Samples at absolute indices ``>= total_samples`` hold the chunk's last
in-range sample, so every chunk of a fixed-shape chunk walk
(``chunk_count`` chunks of ``n`` samples) renders with one static shape —
including the ragged final chunk.  ``t0`` may be a traced value (e.g. a
``lax.scan`` chunk counter); in-range samples are bit-identical to
``render`` at the same indices.  Requires ``t0 < total_samples`` (at
least one in-range sample per chunk) — the walk ``chunk_count``
prescribes never violates this.

This is the entry point for *external* fixed-shape pipelines (e.g. a
pre-sized ring buffer).  The scanned fleet engine itself conditions the
ragged tail at its natural length instead (``pdu.condition`` pads the
trailing partial controller interval internally), so its state and
aggregates never see whole pad intervals.
"""


def chunk_count(s: Scenario, chunk_samples: int) -> int:
    """Static number of ``chunk_samples``-sample chunks covering the
    scenario — the fixed walk length for ``render_padded`` pipelines or a
    ``lax.scan`` over same-shaped chunks."""
    if chunk_samples <= 0:
        raise ValueError(f"chunk_samples must be positive, got {chunk_samples}")
    return -(-s.total_samples // int(chunk_samples))


def render_trace(s: Scenario) -> tuple[jax.Array, float]:
    """Render the whole scenario; returns ``(trace, dt)`` like the legacy API."""
    return render(s, 0, s.total_samples), s.dt


# ------------------------------------------------- compiled phase timelines


def from_phase_timeline(
    durations_s,
    powers,
    sample_hz: float,
    *,
    edge_time_s: float = 0.1,
    noise_seed: int | None = None,
    noise_std: float = 0.01,
) -> Scenario:
    """Compile an explicit phase timeline into a segment-table scenario.

    Matches ``trace.phase_timeline_trace``'s discretization: each phase gets
    ``max(round(duration*hz), 1)`` samples and transitions get boxcar edges.
    ``powers`` may be ``(K,)`` or a per-rack ``(R, K)``.  Measurement noise
    at ``noise_std`` is enabled by passing ``noise_seed``.
    """
    durations = np.asarray(durations_s, np.float64)
    counts = np.maximum(np.round(durations * sample_hz).astype(np.int64), 1)
    bounds = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    powers = jnp.asarray(powers, jnp.float32)
    return Scenario(
        params=None,
        seg_bounds=jnp.asarray(bounds),
        seg_powers=powers,
        seg_noise_std=jnp.asarray(noise_std, jnp.float32),
        sample_hz=float(sample_hz),
        total_samples=int(counts.sum()),
        edge_width=_edge_width(edge_time_s, sample_hz),
        noise_seed=noise_seed,
    )


# ------------------------------------------------------- model-derived racks


def workload_from_model(
    arch: str,
    *,
    hw=None,
    phase_model=None,
    tokens_per_step: float = 2**20,
    min_exposed_fraction: float = 0.08,
    **overrides,
) -> WorkloadParams:
    """Derive a rack workload from an assigned model config's step cost.

    Uses ``configs.registry.step_cost`` (6*N*tokens FLOPs and
    parameter-traffic byte counts) through ``phases.step_phases`` to place
    the iteration wave: the compute plateau lasts the step's busy time and
    the comm window its exposed-collective time.  Well-overlapped small
    models would expose almost nothing, which would erase the square wave
    the grid actually sees (paper Fig. 3), so the exposed fraction is
    floored at ``min_exposed_fraction`` of the busy time.  Checkpoint stalls
    become the periodic deep dips.
    """
    from repro.configs import registry
    from repro.power import phases as P

    hw = hw or P.HardwareConstants()
    pm = phase_model or P.PhaseModel()
    cost = registry.step_cost(arch, tokens_per_step=tokens_per_step)
    d, pw = P.step_phases(cost, hw, pm)
    t_busy = float(d[0])
    t_exposed = max(float(d[1]), min_exposed_fraction * t_busy)
    period = t_busy + t_exposed
    dev = pm.device
    p_idle = dev.p_idle_w / dev.p_peak_w
    knobs = dict(
        iteration_period_s=period,
        comm_fraction=t_exposed / period,
        p_compute=float(pw[0]),
        p_comm=float(pw[1]),
        dip_period_s=(
            pm.checkpoint_every_steps * period if pm.checkpoint_every_steps else NEVER
        ),
        dip_duration_s=pm.checkpoint_stall_s,
        p_dip=p_idle,
        p_idle=p_idle,
        warmup_s=10.0,
    )
    knobs.update(overrides)
    return workload(**knobs)


def scenario_from_model(
    arch: str,
    *,
    duration_s: float = 240.0,
    sample_hz: float = 200.0,
    edge_time_s: float = 0.25,
    noise_seed: int | None = None,
    **kwargs,
) -> Scenario:
    """One rack running one assigned model, as a renderable scenario."""
    return make_scenario(
        workload_from_model(arch, **kwargs),
        duration_s=duration_s,
        sample_hz=sample_hz,
        edge_time_s=edge_time_s,
        noise_seed=noise_seed,
    )


def inference_workload(
    *,
    p_idle: float = 0.15,
    p_peak: float = 0.75,
    diurnal_period_s: float = 600.0,
    diurnal_amp: float = 0.85,
    diurnal_phase_s: float = 0.0,
    iteration_period_s: float = 0.5,
    comm_fraction: float = 0.2,
    **overrides,
) -> WorkloadParams:
    """A serving rack: fast shallow batching ripple under a deep diurnal
    envelope (the Ko & Zhu / Li et al. grid-risk profile)."""
    knobs = dict(
        iteration_period_s=iteration_period_s,
        comm_fraction=comm_fraction,
        p_compute=p_peak,
        p_comm=p_peak * 0.8,
        dip_period_s=NEVER,
        dip_duration_s=0.0,
        p_dip=p_idle,
        p_idle=p_idle,
        warmup_s=5.0,
        diurnal_period_s=diurnal_period_s,
        diurnal_amp=diurnal_amp,
        diurnal_phase_s=diurnal_phase_s,
    )
    knobs.update(overrides)
    return workload(**knobs)


def mixed_campus(
    n_racks: int,
    archs: tuple[str, ...],
    *,
    duration_s: float = 240.0,
    sample_hz: float = 200.0,
    seed: int = 0,
    inference_fraction: float = 0.25,
    stagger_s: float = 30.0,
    stop_fraction: float = 0.15,
    fault_rack_fraction: float = 0.1,
    fault_at_s: float | None = None,
    fault_cascade_s: float = 5.0,
    fault_duration_s: float = 30.0,
    edge_time_s: float = 0.25,
    edge_pad: str = "zero",
    noise_seed: int | None = None,
) -> Scenario:
    """A heterogeneous campus: training racks cycling different assigned
    models, an inference-diurnal block, staggered job starts, a subset of
    early job terminations, and a mid-trace fault cascade rippling across a
    contiguous rack range.  Entirely data — one (R,)-batched scenario."""
    import dataclasses

    rng = np.random.default_rng(seed)
    n_inf = int(round(n_racks * inference_fraction))
    n_train = n_racks - n_inf

    # Assemble the per-rack parameter columns on the host (numpy) and
    # convert each leaf exactly once — a 1024-rack campus is 19 transfers,
    # not 19 x (R+1) tiny device ops.
    as_floats = lambda w: jax.tree_util.tree_map(float, w)
    train_templates = [as_floats(workload_from_model(a)) for a in archs]
    inf_template = as_floats(inference_workload(diurnal_period_s=duration_s / 1.5))
    cols: dict[str, np.ndarray] = {}
    for f in dataclasses.fields(WorkloadParams):
        train_vals = [
            getattr(train_templates[i % len(train_templates)], f.name)
            for i in range(n_train)
        ]
        cols[f.name] = np.asarray(
            train_vals + [getattr(inf_template, f.name)] * n_inf, np.float32
        )
    cols["diurnal_phase_s"][n_train:] = rng.uniform(0.0, duration_s, n_inf)

    cols["t_start_s"] = rng.uniform(0.0, stagger_s, n_racks).astype(np.float32)
    n_stop = int(round(n_racks * stop_fraction))
    stop_idx = rng.choice(n_racks, size=n_stop, replace=False)
    cols["t_end_s"][stop_idx] = rng.uniform(0.7, 0.95, n_stop) * duration_s

    n_fault = int(round(n_racks * fault_rack_fraction))
    if n_fault:
        f0 = duration_s * 0.6 if fault_at_s is None else fault_at_s
        lo = int(rng.integers(0, max(n_racks - n_fault, 1)))
        # cascade: the fault ripples across the contiguous rack range
        cols["fault_at_s"][lo : lo + n_fault] = f0 + np.linspace(
            0.0, fault_cascade_s, n_fault, dtype=np.float32
        )
    cols["fault_duration_s"] = np.full(n_racks, fault_duration_s, np.float32)
    cols["scale"] = (1.0 + 0.05 * rng.uniform(-1.0, 1.0, n_racks)).astype(np.float32)
    params = WorkloadParams(**{k: jnp.asarray(v) for k, v in cols.items()})
    return make_scenario(
        params,
        duration_s=duration_s,
        sample_hz=sample_hz,
        edge_time_s=edge_time_s,
        edge_pad=edge_pad,
        noise_seed=noise_seed,
    )
