"""Grid-region layer: multi-campus conditioning at a point of interconnection.

The paper conditions power at the rack level; what the *grid* sees is the
aggregate of many campuses at a point of interconnection (POI).  This
module scales the scanned conditioner from one campus to a region:

* ``GridRegion`` — N campuses (each a ``power.scenario.Scenario``, with
  heterogeneous rack counts and fault soups) plus their POI weights, the
  POI coupling constants, and the wide-area oscillation band table.
* ``condition_region`` — the region engines behind the ``fleet.condition``
  facade.  The *sequential* engine loops campuses through the scanned
  conditioner and accumulates the POI left-to-right; the *sharded* engine
  stacks the campuses and runs them in parallel under ``shard_map`` over a
  2-D (campus, data) mesh, reducing campus→POI aggregates with in-scan
  ``psum`` collectives.  One campus per campus-shard keeps the ``psum``
  reduction order equal to the sequential left-to-right sum, so the two
  engines are bitwise identical on the POI aggregates (the parity suite
  pins this on a forced 8-device CPU mesh).  The rack axis stays whole
  per campus: per-rack ``psum`` reassociates the campus mean and breaks
  bitwise parity (EXPERIMENTS §Grid-region), so the "data" axis is
  reserved for the GSPMD ``shard_racks`` paths and left unmentioned
  (replicated) here.
* ``poi_response`` — first-order grid coupling: a swing-equation style
  frequency-deviation sensitivity and a proportional voltage-deviation
  estimate at the POI.
* Mode detection — a second Goertzel ``compliance.SpectrumBank`` dense
  over sub-Hz wide-area oscillation bands; per-band verdicts are folded
  into the POI compliance report (``compliance.with_mode_verdicts``).
  Synchronized checkpoint stalls across campuses ring the inter-area band;
  staggering the campus schedules cancels it (see ``checkpoint_region``
  and EXPERIMENTS §Grid-region).
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import compliance, fleet, pdu, profiling as _prof
from repro.sharding import rules
from repro.utils import pytree_dataclass, static_field


# ------------------------------------------------------------ POI coupling


@dataclasses.dataclass(frozen=True)
class POIConfig:
    """First-order coupling constants at the interconnection node.

    Hashable static config (like ``compliance.SpectrumBank``), not traced
    data: it rides in jit closures and engine cache keys.  The swing-style
    model is deliberately first-order — enough to translate a per-unit POI
    power excursion into the frequency/voltage deviations an operator
    would meter, not a network simulation.
    """

    inertia_s: float = 8.0  # M: effective inertia constant [s]
    damping: float = 1.5  # D: load-frequency damping [pu power / pu freq]
    f0_hz: float = 60.0  # nominal system frequency
    v_sens: float = 0.05  # |dV| / dP voltage sensitivity [pu/pu, local bus]
    # The region's rated power as a fraction of the interconnection's
    # frequency-responsive capacity: frequency is a system-wide state, so
    # the region's per-unit excursion is scaled by this before it forces
    # the swing dynamics (voltage deviation stays on the local bus base).
    region_fraction: float = 0.01

    @staticmethod
    def create(**kw) -> "POIConfig":
        return POIConfig(**kw)


class POIResponse(NamedTuple):
    freq_dev_hz: jax.Array  # (T,) frequency deviation at the POI [Hz]
    volt_dev: jax.Array  # (T,) voltage deviation at the POI [pu]
    max_freq_dev_hz: jax.Array  # () worst |freq_dev|
    max_volt_dev: jax.Array  # () worst |volt_dev|


def poi_response(
    poi_power: jax.Array,
    poi: POIConfig,
    dt: float,
    p_ref: jax.Array | None = None,
) -> POIResponse:
    """Swing-style POI sensitivity:  M df/dt = -(ΔP + D·f),  ΔV = -k_v·ΔP.

    ``poi_power`` is the per-unit POI trace; deviations are taken against
    ``p_ref`` (default: the trace mean — the scheduled interchange a
    balanced dispatch would net out).  Per-unit frequency integrates
    through a forward-Euler scan and scales by ``f0_hz``.
    """

    def build():
        @jax.jit
        def run(p, ref):
            dp = p - ref
            a = jnp.float32(dt / poi.inertia_s)
            damp = jnp.float32(poi.damping)
            dp_sys = jnp.float32(poi.region_fraction) * dp

            def step(f, d):
                f2 = f + a * (-d - damp * f)
                return f2, f2

            with _prof.scope("swing"):
                _, fdev = jax.lax.scan(step, jnp.float32(0.0), dp_sys)
            freq = fdev * jnp.float32(poi.f0_hz)
            volt = -jnp.float32(poi.v_sens) * dp
            return POIResponse(
                freq_dev_hz=freq,
                volt_dev=volt,
                max_freq_dev_hz=jnp.max(jnp.abs(freq)),
                max_volt_dev=jnp.max(jnp.abs(volt)),
            )

        return run

    run = fleet._cached_engine(("poi_response", poi, float(dt)), build)
    poi_power = jnp.asarray(poi_power, jnp.float32)
    ref = jnp.mean(poi_power) if p_ref is None else jnp.asarray(p_ref, jnp.float32)
    return run(poi_power, ref)


# ----------------------------------------------------------- mode detector


@dataclasses.dataclass(frozen=True)
class ModeBand:
    """One wide-area oscillation band: flag when any monitored line inside
    [lo_hz, hi_hz) exceeds ``threshold`` (normalized one-sided magnitude,
    same units as ``compliance.normalized_spectrum``)."""

    name: str
    lo_hz: float
    hi_hz: float
    threshold: float


# Classic wide-area ranges: inter-area modes live well below 1 Hz, local /
# intra-plant modes up to a few Hz.  Thresholds are per-unit-of-rating
# magnitudes calibrated on the synchronized-checkpoint scenario
# (EXPERIMENTS §Grid-region): synchronized campuses ring the inter-area
# band an order of magnitude above threshold; staggered campuses sit well
# below it.
DEFAULT_MODE_BANDS = (
    ModeBand("inter_area", 0.1, 1.0, 0.005),
    ModeBand("local_plant", 1.0, 3.0, 0.005),
)


def mode_bank(
    n_total: int,
    dt: float,
    bands: tuple[ModeBand, ...] = DEFAULT_MODE_BANDS,
    *,
    max_lines_per_band: int = 96,
) -> compliance.SpectrumBank:
    """A Goertzel bank dense over the mode bands of a length-``n_total``
    trace: every DFT bin inside each band (evenly strided down to
    ``max_lines_per_band`` lines when a band spans more bins), Hann
    windowed so finalized magnitudes match ``normalized_spectrum``."""
    bins: set[int] = set()
    for b in bands:
        k_lo = max(int(np.ceil(b.lo_hz * n_total * dt)), 1)
        k_hi = min(int(np.floor(b.hi_hz * n_total * dt)), n_total // 2)
        if k_hi < k_lo:
            continue
        ks = np.arange(k_lo, k_hi + 1, dtype=np.int64)
        if ks.size > max_lines_per_band:
            ks = np.unique(
                np.round(np.linspace(k_lo, k_hi, max_lines_per_band)).astype(np.int64)
            )
        bins.update(int(x) for x in ks)
    return compliance.SpectrumBank(
        bins=tuple(sorted(bins)), modulus=int(n_total), dt=float(dt), window="hann"
    )


def mode_verdicts(
    bank: compliance.SpectrumBank,
    obs: compliance.SpectrumObserver,
    bands: tuple[ModeBand, ...],
) -> tuple[jax.Array, jax.Array]:
    """(mags, ok) per band: worst monitored-line magnitude inside each band
    and its threshold verdict.  A band with no line on this trace's grid
    (trace too short to resolve it) reports magnitude 0 and passes."""
    freqs, mags = compliance.spectrum_observer_finalize(bank, obs)
    out_m, out_ok = [], []
    for b in bands:
        sel = (freqs >= b.lo_hz) & (freqs < b.hi_hz)
        if not np.any(sel):
            out_m.append(jnp.float32(0.0))
            out_ok.append(jnp.asarray(True))
            continue
        m = jnp.max(jnp.where(jnp.asarray(sel), mags, 0.0))
        out_m.append(m)
        out_ok.append(m <= b.threshold)
    return jnp.stack(out_m), jnp.stack(out_ok)


# -------------------------------------------------------------- GridRegion


@pytree_dataclass
class GridRegion:
    """N campuses aggregated at a point of interconnection.

    ``campuses`` is a tuple of per-campus ``Scenario`` pytrees (traced
    children — heterogeneous rack counts and fault soups are fine for the
    sequential engine; the sharded engine additionally needs the campuses
    stackable: same statics, rack count, and fault-schedule shape).
    ``weights`` is the (C,) per-unit POI share of each campus (the POI
    trace is ``sum_c w_c * campus_c``); POI coupling and the mode-band
    table are static config.  Build with ``region(...)``.
    """

    campuses: tuple
    weights: jax.Array
    names: tuple = static_field(default=())
    poi: POIConfig = static_field(default=POIConfig())
    bands: tuple = static_field(default=DEFAULT_MODE_BANDS)

    @property
    def n_campuses(self) -> int:
        return len(self.campuses)

    @property
    def sample_hz(self) -> float:
        return self.campuses[0].sample_hz

    @property
    def total_samples(self) -> int:
        return self.campuses[0].total_samples

    @property
    def n_racks(self) -> tuple:
        return tuple(c.n_racks or 1 for c in self.campuses)


def region(
    campuses,
    *,
    weights=None,
    names=None,
    poi: POIConfig | None = None,
    bands: tuple[ModeBand, ...] = DEFAULT_MODE_BANDS,
    salt_noise: bool = True,
) -> GridRegion:
    """Build a ``GridRegion`` from per-campus scenarios.

    Campuses must share the sample rate and trace length (one POI clock).
    ``weights`` defaults to the rack-count share, so the POI trace is the
    per-unit mean over the region's racks.  ``salt_noise`` XORs a distinct
    ``noise_salt`` into each campus that has measurement noise but no salt
    yet — campuses built from the same workload spec then draw
    decorrelated noise even though the sharded engine requires them to
    share the static ``noise_seed``.
    """
    from repro.power import scenario as SC

    campuses = tuple(campuses)
    if not campuses:
        raise ValueError("a region needs at least one campus")
    hz, total = campuses[0].sample_hz, campuses[0].total_samples
    for i, c in enumerate(campuses[1:], 1):
        if c.sample_hz != hz or c.total_samples != total:
            raise ValueError(
                f"campus {i} runs {c.sample_hz} Hz x {c.total_samples} "
                f"samples but campus 0 runs {hz} Hz x {total}; one POI "
                "clock requires a shared rate and length"
            )
    if salt_noise:
        campuses = tuple(
            c if (c.noise_seed is None or c.noise_salt is not None)
            else SC.with_noise_salt(c, i)
            for i, c in enumerate(campuses)
        )
    if weights is None:
        w = np.asarray([c.n_racks or 1 for c in campuses], np.float32)
        weights = w / w.sum()
    weights = jnp.asarray(weights, jnp.float32)
    if weights.shape != (len(campuses),):
        raise ValueError(
            f"weights shape {weights.shape} != ({len(campuses)},)")
    names = tuple(names) if names else tuple(
        f"campus{i}" for i in range(len(campuses)))
    if len(names) != len(campuses):
        raise ValueError(f"{len(names)} names for {len(campuses)} campuses")
    return GridRegion(
        campuses=campuses,
        weights=weights,
        names=names,
        poi=poi if poi is not None else POIConfig(),
        bands=tuple(bands),
    )


def checkpoint_region(
    n_campuses: int = 4,
    n_racks: int = 64,
    *,
    duration_s: float = 200.0,
    sample_hz: float = 50.0,
    dip_period_s: float = 8.0,
    dip_duration_s: float = 2.0,
    p_dip: float = 0.12,
    stagger: bool = False,
    noise_seed: int | None = 0,
    poi: POIConfig | None = None,
    bands: tuple[ModeBand, ...] = DEFAULT_MODE_BANDS,
) -> GridRegion:
    """The wide-area oscillation testbench: N identical campuses whose only
    periodic structure is the checkpoint stall (compute plateau, no
    comm wave), checkpointing every ``dip_period_s``.

    ``stagger=False`` checkpoints every campus in lockstep — the POI rings
    the dip fundamental (1/``dip_period_s``, inside the inter-area band at
    the defaults) and its harmonics.  ``stagger=True`` offsets campus c's
    schedule by ``c/N`` of the dip period, cancelling every harmonic that
    is not a multiple of N (and the N-th falls on a sinc null of the dip
    duty cycle at the defaults) — the mode detector passes.
    """
    from repro.power import scenario as SC

    campuses = []
    for c in range(n_campuses):
        off = (c * dip_period_s / n_campuses) if stagger else 0.0
        w = SC.workload(
            comm_fraction=0.0,
            p_comm=0.92,
            dip_period_s=dip_period_s,
            dip_duration_s=dip_duration_s,
            p_dip=p_dip,
            warmup_s=2.0,
            t_start_s=np.full((n_racks,), off, np.float32),
        )
        campuses.append(SC.make_scenario(
            w, duration_s=duration_s, sample_hz=sample_hz,
            edge_pad="clamp", noise_seed=noise_seed,
        ))
    return region(campuses, poi=poi, bands=bands)


def synchronized_region(**kw) -> GridRegion:
    """``checkpoint_region`` with lockstep campus checkpoints (rings the
    inter-area mode band)."""
    return checkpoint_region(stagger=False, **kw)


def staggered_region(**kw) -> GridRegion:
    """``checkpoint_region`` with campus checkpoints staggered across the
    dip period (the mode cancels at the POI)."""
    return checkpoint_region(stagger=True, **kw)


# ---------------------------------------------------------- POI observers


class _POIObservers(NamedTuple):
    """Streaming compliance state for the POI traces: ramp + spec-line
    observers on the unconditioned/conditioned POI, plus the mode-band
    Goertzel fold on the conditioned POI."""

    ramp_rack: compliance.RampObserver
    ramp_grid: compliance.RampObserver
    spec_rack: compliance.SpectrumObserver
    spec_grid: compliance.SpectrumObserver
    modes: compliance.SpectrumObserver


def _poi_observers_init(bank, mbank) -> _POIObservers:
    return _POIObservers(
        ramp_rack=compliance.ramp_observer_init(),
        ramp_grid=compliance.ramp_observer_init(),
        spec_rack=compliance.spectrum_observer_init(bank),
        spec_grid=compliance.spectrum_observer_init(bank),
        modes=compliance.spectrum_observer_init(mbank),
    )


def _poi_observers_update(po, bank, mbank, pr, pg, dt) -> _POIObservers:
    return _POIObservers(
        ramp_rack=compliance.ramp_observer_update(po.ramp_rack, pr, dt),
        ramp_grid=compliance.ramp_observer_update(po.ramp_grid, pg, dt),
        spec_rack=compliance.spectrum_observer_update(bank, po.spec_rack, pr),
        spec_grid=compliance.spectrum_observer_update(bank, po.spec_grid, pg),
        modes=compliance.spectrum_observer_update(mbank, po.modes, pg),
    )


def _poi_fold(bank, mbank, chunk, n_full, rem, dt):
    """Cached jitted fold of the POI observers over materialized POI traces
    with the SAME chunk partition the sharded engine folds in-scan — the
    Goertzel accumulation is chunk-partition sensitive, so matching the
    partition is part of the bitwise parity contract."""

    def build():
        @jax.jit
        def run(pr, pg):
            po = _poi_observers_init(bank, mbank)
            if n_full:
                def body(po, xs):
                    cr, cg = xs
                    with _prof.scope("poi"):
                        return _poi_observers_update(
                            po, bank, mbank, cr, cg, dt), None

                po, _ = jax.lax.scan(
                    body, po,
                    (pr[: n_full * chunk].reshape(n_full, chunk),
                     pg[: n_full * chunk].reshape(n_full, chunk)),
                )
            if rem:
                with _prof.scope("poi"):
                    po = _poi_observers_update(
                        po, bank, mbank,
                        pr[n_full * chunk:], pg[n_full * chunk:], dt,
                    )
            return po

        return run

    return fleet._cached_engine(
        ("poi_fold", bank, mbank, chunk, n_full, rem, dt), build)


# -------------------------------------------------------------- engines


def _assemble_region_result(
    cfg, reg, grid_spec, per, campus_rack, campus_grid, soc_mean,
    health_trace, ess_frac, max_qp, poi_rack, poi_grid, po, bank, mbank,
    sm_trace=None,
) -> fleet.ConditioningResult:
    rep_rack = compliance.report_from_observers(
        grid_spec, po.ramp_rack, bank, po.spec_rack)
    rep_grid = compliance.report_from_observers(
        grid_spec, po.ramp_grid, bank, po.spec_grid)
    mags, ok = mode_verdicts(mbank, po.modes, reg.bands)
    rep_poi = compliance.with_mode_verdicts(rep_grid, mags, ok)
    resp = poi_response(poi_grid, reg.poi, cfg.sample_dt)
    return fleet.ConditioningResult(
        campus_rack=campus_rack,
        campus_grid=campus_grid,
        report_rack=rep_rack,
        report_grid=rep_poi,
        soc_mean=soc_mean,
        state=tuple(p.state for p in per),
        max_qp_residual=max_qp,
        health_trace=health_trace,
        ess_online_frac=ess_frac,
        safemode_trace=sm_trace,
        poi_rack=poi_rack,
        poi_grid=poi_grid,
        report_poi=rep_poi,
        poi_freq_dev=resp.freq_dev_hz,
        poi_volt_dev=resp.volt_dev,
        per_campus=tuple(per),
        weights=reg.weights,
        grid_spec=grid_spec,
        bank=bank,
        observers=fleet._Observers(
            po.ramp_rack, po.ramp_grid, po.spec_rack, po.spec_grid),
    )


def _oracle_mesh() -> jax.sharding.Mesh:
    """A (campus=1, data=1) mesh on the first local device — exists on any
    host, so the sequential oracle can run each campus through the same
    shard_map-compiled engine the sharded path uses.  XLA compiles a
    shard_map body slightly differently from the plain-jit scanned engine
    (~1 ulp drift in the conditioned trace on CPU), so staying inside
    shard_map for BOTH region engines is what makes them bitwise identical
    on campus and POI aggregates (the parity contract)."""
    return rules.region_mesh(1, devices=jax.devices()[:1])


def condition_region_sequential(
    cfg: pdu.PDUConfig,
    reg: GridRegion,
    grid_spec: compliance.GridSpec,
    *,
    soc0: float = 0.5,
    qp_iters: int = 30,
    chunk_intervals: int = 16,
    states=None,
    start_sample: int = 0,
    stop_sample: int | None = None,
) -> fleet.ConditioningResult:
    """The region oracle: each campus through the region engine in turn on
    a single device, POI accumulated left-to-right (the order the sharded
    engine's ``psum`` reduces in), POI observers folded with the engines'
    shared chunk partition.  Handles heterogeneous rack counts; wall-clock
    scales with N campuses.  Bitwise identical to
    ``condition_region_sharded`` on campus and POI aggregates."""
    C = reg.n_campuses
    states = (None,) * C if states is None else tuple(states)
    if len(states) != C:
        raise ValueError(f"{len(states)} states for {C} campuses")
    geom = fleet._chunk_geometry(
        cfg, reg, chunk_intervals, start_sample, stop_sample)
    k, chunk, start, stop, t_total, n_full, rem, n_ctrl = geom
    mesh1 = _oracle_mesh()
    one = jnp.ones((1,), jnp.float32)
    per = []
    for c, scen in enumerate(reg.campuses):
        sub = GridRegion(
            campuses=(scen,), weights=one, names=(reg.names[c],),
            poi=reg.poi, bands=reg.bands,
        )
        r = condition_region_sharded(
            cfg, sub, grid_spec, mesh1, soc0=soc0, qp_iters=qp_iters,
            chunk_intervals=chunk_intervals, states=(states[c],),
            start_sample=start, stop_sample=stop,
        )
        per.append(r.per_campus[0])
    w = reg.weights
    add = lambda a, b: a + b
    poi_rack = functools.reduce(
        add, [w[c] * per[c].campus_rack for c in range(C)])
    poi_grid = functools.reduce(
        add, [w[c] * per[c].campus_grid for c in range(C)])
    bank = fleet._make_bank(grid_spec, cfg, t_total)
    mbank = mode_bank(t_total, cfg.sample_dt, reg.bands)
    po = _poi_fold(bank, mbank, chunk, n_full, rem, cfg.sample_dt)(
        poi_rack, poi_grid)
    return _assemble_region_result(
        cfg, reg, grid_spec, per,
        campus_rack=jnp.stack([p.campus_rack for p in per]),
        campus_grid=jnp.stack([p.campus_grid for p in per]),
        soc_mean=jnp.stack([p.soc_mean for p in per]),
        health_trace=jnp.stack([p.health_trace for p in per]),
        ess_frac=jnp.stack([p.ess_online_frac for p in per]),
        max_qp=functools.reduce(
            jnp.maximum, [p.max_qp_residual for p in per]),
        poi_rack=poi_rack, poi_grid=poi_grid, po=po, bank=bank, mbank=mbank,
        sm_trace=jnp.stack([p.safemode_trace for p in per]),
    )


# A campus's state or scenario with the campus axis added, on the device it
# is on.  The output is a fresh buffer, so the engine's donation of the
# stacked state never takes the caller's (a checkpoint, or the state a
# harness restarts from).
_lift = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x[None], t))


def _stack_states(states, mesh):
    """The per-campus trees as one tree sharded ``P("campus")`` over
    ``mesh``: each lifted on its own campus's device (moved there first
    unless it already is), the pieces joined on the host.  No program spans
    two campuses."""
    sharding = NamedSharding(mesh, P("campus"))
    per_dev = []  # one lifted tree per device of the mesh, campus-major
    for st, row in zip(states, rules.campus_rows(mesh)):
        piece = _lift(jax.device_put(st, row[0]))
        per_dev += [piece] + [jax.device_put(piece, d) for d in row[1:]]
    return jax.tree_util.tree_map(
        lambda *xs: jax.make_array_from_single_device_arrays(
            (len(states),) + xs[0].shape[1:], sharding, list(xs)),
        *per_dev)


# The stacked scenarios of the last region conditioned on each mesh, beside
# a weak reference to that region: a region's campuses do not change from
# call to call, so its calls after the first reuse the stack.
_SCENARIO_STACKS: dict = {}


def _stack_campuses(reg: GridRegion, mesh):
    """The campuses' scenarios as one campus-sharded scenario, each campus
    on its own devices (``_stack_states``), stacked once per region and
    mesh."""
    held, scen_s = _SCENARIO_STACKS.get(mesh, (None, None))
    if held is not None and held() is reg:
        return scen_s
    try:
        scen_s = _stack_states(reg.campuses, mesh)
    except (ValueError, TypeError) as e:
        raise ValueError(
            "the sharded region engine stacks campuses into one batched "
            "scenario, which requires every campus to share its structure "
            "(statics, rack count, fault-schedule shape); heterogeneous "
            f"regions run the sequential engine (mesh=None): {e}"
        ) from None
    _SCENARIO_STACKS[mesh] = (weakref.ref(reg), scen_s)
    return scen_s


def _split_campuses(tree, devices):
    """Campus ``c``'s shards of the campus-sharded arrays of ``tree``, as
    single-device arrays on ``devices[c]``: read from the shards each
    device already holds, so nothing is gathered or copied."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    by_dev = [{s.device: s.data for s in x.addressable_shards} for x in leaves]
    return [jax.tree_util.tree_unflatten(treedef, [b[d] for b in by_dev])
            for d in devices]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _campus_view(tree, t_total, n_ctrl):
    """One campus's (state, observers, aggregates) shard with the campus
    axis dropped and the padded traces cut to the window; runs on the
    campus's own device."""
    st, obs, camp = jax.tree_util.tree_map(lambda x: x[0], tree)
    return st, obs, camp._replace(
        campus_rack=camp.campus_rack[:t_total],
        campus_grid=camp.campus_grid[:t_total],
        soc_mean=camp.soc_mean[:n_ctrl],
        ess_online_frac=camp.ess_online_frac[:n_ctrl],
    )


def _region_engine(cfg, qp_iters, chunk, k, n_full, rem, mesh, bank, mbank):
    """Cached jitted shard_map engine: every campus's scan runs in parallel
    on its own campus-shard; per-chunk POI aggregates reduce with in-scan
    ``psum`` over the "campus" axis (bitwise equal to the left-to-right
    sequential sum — one campus per shard).  Everything is *manual* over
    the campus axis and replicated over the rest of the mesh: no auto
    axes and no in-body sharding constraints (``rules.shard_map_compat``)."""
    caxis = "campus"

    def build():
        def shard_body(scen_s, st_s, w_s, start):
            take0 = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
            scen, st, wl = take0(scen_s), take0(st_s), w_s[0]

            def poi_fold(po, ch):
                with _prof.scope("poi"):
                    pr = jax.lax.psum(wl * ch.campus_rack, caxis)
                    pg = jax.lax.psum(wl * ch.campus_grid, caxis)
                    po2 = _poi_observers_update(
                        po, bank, mbank, pr, pg, cfg.sample_dt)
                return po2, (pr, pg)

            st, camp, obs, po, (prs, pgs) = fleet._scan_chunks(
                cfg, scen, st, start, bank, chunk=chunk, n_full=n_full,
                rem=rem, qp_iters=qp_iters,
                poi=(lambda: _poi_observers_init(bank, mbank), poi_fold))
            lift = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
            return (lift(st), lift(camp), lift(obs), fleet._cat(prs),
                    fleet._cat(pgs), po)

        f = rules.shard_map_compat(
            shard_body, mesh,
            in_specs=(P(caxis), P(caxis), P(caxis), P()),
            out_specs=(P(caxis), P(caxis), P(caxis), P(), P(), P()),
        )
        return jax.jit(f, donate_argnums=(1,))

    return fleet._cached_engine(
        fleet._engine_key(
            cfg, "region", qp_iters, chunk, k, n_full, rem, mesh, bank, mbank
        ),
        build,
    )


def condition_region_sharded(
    cfg: pdu.PDUConfig,
    reg: GridRegion,
    grid_spec: compliance.GridSpec,
    mesh: jax.sharding.Mesh,
    *,
    soc0: float = 0.5,
    qp_iters: int = 30,
    chunk_intervals: int = 16,
    states=None,
    start_sample: int = 0,
    stop_sample: int | None = None,
) -> fleet.ConditioningResult:
    """Every campus in parallel under ``shard_map``: one jitted dispatch
    conditions the whole region, with the POI reduced by in-scan ``psum``.
    Requires a mesh with a "campus" axis of exactly ``n_campuses`` shards
    (``rules.region_mesh``) and stackable campuses; bitwise equal to
    ``condition_region_sequential`` on campus and POI aggregates.  Campus
    ``c``'s own results (``per_campus[c]``, ``state[c]``) live on the first
    device of its mesh row (``rules.campus_rows``), and a resume state may
    sit anywhere: each is moved to its campus's device once."""
    C = reg.n_campuses
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if "campus" not in axis_sizes:
        raise ValueError(
            f"mesh axes {mesh.axis_names} lack the 'campus' axis; build the "
            "region mesh with rules.region_mesh(n_campuses)")
    if axis_sizes["campus"] != C:
        raise ValueError(
            f"mesh has {axis_sizes['campus']} campus shards for {C} "
            "campuses; exactly one campus per shard keeps the psum "
            "reduction order equal to the sequential left-to-right sum "
            "(the bitwise-parity contract)")
    with _prof.span("prepare"):
        for scen in reg.campuses:
            fleet._check_scenario_rate(scen, cfg)
            fleet._check_scenario_faults(scen, cfg)
        geom = fleet._chunk_geometry(
            cfg, reg, chunk_intervals, start_sample, stop_sample)
        k, chunk, start, stop, t_total, n_full, rem, n_ctrl = geom

        states = (None,) * C if states is None else tuple(states)
        if len(states) != C:
            raise ValueError(f"{len(states)} states for {C} campuses")
        if any(s is None for s in states):
            if not all(s is None for s in states):
                raise ValueError(
                    "per-campus resume states must be all-None (fresh start) "
                    "or all present")

            states = tuple(fleet._fresh_state(cfg, scen, start, soc0)
                           for scen in reg.campuses)
        # Campus c's leaves stay on row c of the mesh, both ways.
        scen_s = _stack_campuses(reg, mesh)
        st_s = _stack_states(states, mesh)

        bank = fleet._make_bank(grid_spec, cfg, t_total)
        mbank = mode_bank(t_total, cfg.sample_dt, reg.bands)
        run = _region_engine(
            cfg, qp_iters, chunk, k, n_full, rem, mesh, bank, mbank)
    with _prof.span("engine"):
        st_f, camp, obs_s, poi_rack, poi_grid, po = run(
            scen_s, st_s, reg.weights, jnp.asarray(start, jnp.int32))

    # Its own name, so idle under the region's slices, assembly and POI
    # reports is told apart from the per-campus ``repro.finish`` inside.
    with _prof.span("region_finish"):
        campus_rack = camp.campus_rack[:, :t_total]
        campus_grid = camp.campus_grid[:, :t_total]
        soc_mean = camp.soc_mean[:, :n_ctrl]
        ess_frac = camp.ess_online_frac[:, :n_ctrl]
        views = [
            _campus_view(t, t_total, n_ctrl)
            for t in _split_campuses(
                (st_f, obs_s, camp), rules.campus_rows(mesh)[:, 0])
        ]
        per = [
            fleet._finish_streaming(
                cfg, grid_spec, st, cp.campus_rack, cp.campus_grid,
                cp.soc_mean, cp.max_qp_residual, bank, obs, cp.health,
                cp.ess_online_frac, cp.safemode,
            )
            for st, obs, cp in views
        ]
        return _assemble_region_result(
            cfg, reg, grid_spec, per,
            campus_rack=campus_rack,
            campus_grid=campus_grid,
            soc_mean=soc_mean,
            health_trace=camp.health,
            ess_frac=ess_frac,
            max_qp=jnp.max(camp.max_qp_residual),
            poi_rack=poi_rack[:t_total],
            poi_grid=poi_grid[:t_total],
            po=po, bank=bank, mbank=mbank,
            sm_trace=camp.safemode,
        )


def condition_region(
    cfg: pdu.PDUConfig,
    reg: GridRegion,
    grid_spec: compliance.GridSpec,
    *,
    mesh: jax.sharding.Mesh | None = None,
    **kwargs,
) -> fleet.ConditioningResult:
    """Region dispatch behind ``fleet.condition``: a mesh selects the
    sharded shard_map engine, ``mesh=None`` the sequential oracle."""
    if mesh is not None:
        return condition_region_sharded(cfg, reg, grid_spec, mesh, **kwargs)
    return condition_region_sequential(cfg, reg, grid_spec, **kwargs)
