"""Fleet-scale aggregation (paper Appendix D, Fig. 13).

The campus load is the sum of per-rack loads; the DFT is linear, so for N
racks in synchrony  P_IT(t) = N * P_i(t)  and  S_IT(f) = N * S_i(f).
Per-rack compliance therefore composes: a hall of EasyRider racks meets the
same (beta, alpha, f_c) budget in aggregate.

This module simulates heterogeneous fleets — per-rack phase offsets
(staggered schedulers), per-rack power scales, rack failures mid-trace —
with the rack dimension vectorized (racks ride in the trailing axis of
every core function, which the Pallas kernels map onto the 128-wide lane
dimension).  For very large fleets the rack axis can be sharded over the
same device mesh the trainer uses (the ``mesh`` argument of ``condition``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compliance, health as hlt, pdu, profiling as _prof, \
    safemode as smode
from repro.sharding.rules import shard_racks_in_jit


def synchronous_aggregate(rack_power: jax.Array, n_racks: int) -> jax.Array:
    """Eq. 19: P_IT = N * P_i for lockstep racks (per-unit of campus rating)."""
    return rack_power  # per-unit traces are scale-invariant (Eq. 20)


def staggered_fleet(
    rack_trace: jax.Array,  # (T,)
    n_racks: int,
    key: jax.Array,
    *,
    max_offset_samples: int = 0,
    scale_jitter: float = 0.0,
) -> jax.Array:
    """(T, n_racks) traces: rolled copies with optional per-rack scaling."""
    k1, k2 = jax.random.split(key)
    if max_offset_samples > 0:
        offsets = jax.random.randint(k1, (n_racks,), 0, max_offset_samples)
    else:
        offsets = jnp.zeros((n_racks,), jnp.int32)
    scales = 1.0 + scale_jitter * jax.random.uniform(k2, (n_racks,), minval=-1.0, maxval=1.0)

    def one(off, sc):
        return jnp.roll(rack_trace, off) * sc

    return jax.vmap(one, out_axes=1)(offsets, scales)


def apply_failures(
    traces: jax.Array,  # (T, R)
    fail_times: jax.Array,  # (R,) sample index at which the rack drops to idle
    p_idle: float = 0.1,
) -> jax.Array:
    """Racks drop to idle power at their failure time (-1 = never).

    Compatibility shim: scripted rack power loss is first-class scenario
    data now (``power.faults`` — attach a ``FaultSchedule`` to the scenario
    and the renderer applies it chunk-bitwise).  This helper packs the old
    fail-time vector into a single-episode schedule and stamps it onto an
    already-materialized trace block; prefer ``scenario.attach_faults`` for
    anything new.
    """
    from repro.power import faults as FLT

    t, r = traces.shape
    ft = np.asarray(fail_times)
    sched = FLT.schedule_from_episodes(
        r, rack=[(i, int(ft[i]), t) for i in range(r) if ft[i] >= 0],
        p_fault=p_idle,
    )
    return jnp.where(FLT.rack_down(sched, 0, t), p_idle, traces)


class ConditioningResult(NamedTuple):
    """The one result type every conditioning engine returns.

    Optional fields are ``None`` when the producing engine does not track
    them: the one-shot engine has no streaming state or observers, the
    scanned engine never materializes per-rack grid traces, and the POI /
    per-campus fields exist only for grid regions (``core.grid``, where the
    campus aggregates gain a leading ``(C,)`` campus axis).
    """

    campus_rack: jax.Array = None  # (T,) mean per-unit unconditioned load
    campus_grid: jax.Array = None  # (T,) mean per-unit conditioned load
    report_rack: compliance.ComplianceReport = None
    report_grid: compliance.ComplianceReport = None
    # Per-rack wear report; when the config does not track health this is
    # the report of an empty history (zero cycles/fade, INFINITE projected
    # lifetime — serialize via ``health.fleet_summary(..., json_safe=True)``).
    health: hlt.HealthReport = None
    # --- one-shot engine extras
    grid_traces: jax.Array = None  # (T, R) conditioned per-rack
    # --- scanned engine extras
    soc_mean: jax.Array = None  # (n_ctrl,) fleet-mean SoC per interval
    state: pdu.PDUState = None  # final PDU state (the stream can resume);
    #   a grid region carries a tuple of per-campus states instead.
    max_qp_residual: jax.Array = None  # worst QP primal residual seen
    health_trace: jax.Array = None  # (n_chunks, 3) [mean EFC, max fade, max DoD]
    # (n_ctrl,) fraction of ESS units online per control interval (ones
    # unless the cfg runs degraded_mode under a fault schedule).
    ess_online_frac: jax.Array = None
    # (n_chunks, 6) safe-mode supervisor snapshot per chunk — the
    # ``pdu.CampusChunk.safemode`` rows (zeros unless the cfg runs
    # safemode; grid regions carry a leading campus axis).
    safemode_trace: jax.Array = None
    # --- grid-region extras (``core.grid``)
    poi_rack: jax.Array = None  # (T,) POI unconditioned (weighted campus sum)
    poi_grid: jax.Array = None  # (T,) POI conditioned
    report_poi: compliance.ComplianceReport = None  # POI report + mode verdicts
    poi_freq_dev: jax.Array = None  # (T,) swing-model frequency deviation [Hz]
    poi_volt_dev: jax.Array = None  # (T,) first-order voltage deviation [pu]
    per_campus: tuple = None  # per-campus ConditioningResults
    weights: jax.Array = None  # (C,) campus POI weights
    # --- observability handles (scanned engine) backing ``.report()``
    grid_spec: compliance.GridSpec = None
    bank: compliance.SpectrumBank = None
    observers: "_Observers" = None

    def report(self, which: str = "grid") -> compliance.ComplianceReport:
        """Compliance report, re-derived from the streaming observers.

        ``which`` selects the stream: ``"rack"`` (unconditioned),
        ``"grid"`` (conditioned — the default), or ``"poi"`` (grid regions;
        the conditioned POI stream with mode-band verdicts folded in).
        Engines without observers (the one-shot path) return their stored
        whole-trace report unchanged.
        """
        stored = {"rack": self.report_rack, "grid": self.report_grid,
                  "poi": self.report_poi}
        if which not in stored:
            raise ValueError(
                f"which={which!r} (expected 'rack', 'grid' or 'poi')")
        pre = stored[which]
        if self.observers is None or self.bank is None or self.grid_spec is None:
            return pre
        key = "grid" if which == "poi" else which
        rep = _report_program(self.bank)(
            self.grid_spec,
            getattr(self.observers, f"ramp_{key}"),
            getattr(self.observers, f"spec_{key}"),
            _spectrum_norm(self.bank),
        )
        if pre is not None and pre.mode_mags is not None:
            rep = compliance.with_mode_verdicts(rep, pre.mode_mags, pre.mode_ok)
        return rep

    def safemode_summary(self) -> dict | None:
        """Host-side safe-mode supervisor summary from the final state(s).

        ``None`` when the engine carried no state or the config did not run
        safemode; a grid region sums the per-campus states and keys the
        rack lists by campus index.
        """
        if self.state is None:
            return None
        # NB: PDUState is itself a NamedTuple — only a *plain* tuple means
        # a grid region's per-campus states.
        states = (
            (self.state,)
            if isinstance(self.state, pdu.PDUState)
            else tuple(self.state)
        )
        if any(getattr(st, "safemode", None) is None for st in states):
            return None
        parts = [smode.summary(st.safemode) for st in states]
        out = dict(parts[0])
        if len(parts) > 1:
            for key in ("n_normal", "n_passthrough", "n_quarantined",
                        "passthrough_entries", "quarantine_entries",
                        "readmissions"):
                out[key] = sum(p[key] for p in parts)
            out["worst_resid_streak"] = max(
                p["worst_resid_streak"] for p in parts)
            out["passthrough_racks"] = {
                c: p["passthrough_racks"] for c, p in enumerate(parts)}
            out["quarantined_racks"] = {
                c: p["quarantined_racks"] for c, p in enumerate(parts)}
        return out


def _condition_oneshot_impl(
    cfg: pdu.PDUConfig,
    traces: jax.Array,  # (T, R) per-unit rack traces
    grid_spec: compliance.GridSpec,
    *,
    soc0: float = 0.5,
    qp_iters: int = 60,
    use_plan: bool = True,
    ess_online: jax.Array | None = None,
    ess_weight: jax.Array | None = None,
) -> ConditioningResult:
    """Condition every rack with its own PDU; check campus compliance.

    The per-rack state is fully vectorized (rack axis rides through the
    scans), so this is one fused XLA computation whatever R is.
    ``use_plan=False`` selects the per-rack build+factor controller path
    (the seed cold-start baseline used by benchmarks).

    ``ess_online`` (requires ``cfg.degraded_mode``) is the per-interval ESS
    availability mask — ``(n_ctrl, R)`` rows or one ``(R,)`` mask — with
    the same semantics as ``pdu.condition``; ``ess_weight`` is the
    optional per-sample ``(T, R)`` hardware availability weight
    (``faults.ess_weight``).  NaN sensor-dropout samples in ``traces`` are
    bridged before conditioning, so campus aggregates and compliance stay
    finite under any fault schedule.
    """
    r0 = traces[0]  # init_state bridges NaN (sensor-dark) entries itself
    state = pdu.init_state(cfg, r0, soc0=soc0)
    grid, state_f, telem = pdu.condition(
        cfg, state, traces, qp_iters=qp_iters, use_plan=use_plan,
        ess_online=ess_online, ess_weight=ess_weight,
    )
    if cfg.degraded_mode:
        campus_rack = telem.rack_mean
        on_frac = jnp.mean(telem.ess_online, axis=1)
    else:
        campus_rack = jnp.mean(traces, axis=1)
        on_frac = jnp.ones(telem.soc.shape[0], jnp.float32)
    campus_grid = jnp.mean(grid, axis=1)
    return ConditioningResult(
        grid_traces=grid,
        campus_rack=campus_rack,
        campus_grid=campus_grid,
        report_rack=compliance.check(campus_rack, cfg.sample_dt, grid_spec),
        report_grid=compliance.check(campus_grid, cfg.sample_dt, grid_spec),
        health=hlt.report(
            _health_params(cfg), cfg.ess_params, state_f.health, cfg.sample_dt
        ),
        ess_online_frac=on_frac,
        safemode_trace=(
            smode.chunk_snapshot(state_f.safemode)[None]
            if cfg.safemode else jnp.zeros((1, 6), jnp.float32)
        ),
    )


def _health_params(cfg: pdu.PDUConfig) -> hlt.HealthParams:
    return cfg.health if cfg.health is not None else hlt.HealthParams.create()


# ----------------------------------------------------------------- streaming


class _Observers(NamedTuple):
    """Streaming compliance state folded inside the chunk scan:
    reports come from these, not from re-diffing/FFT-ing materialized
    campus arrays — so compliance is available online however long the
    stream runs (and the cross-chunk boundary ramp is never dropped)."""

    ramp_rack: compliance.RampObserver
    ramp_grid: compliance.RampObserver
    spec_rack: compliance.SpectrumObserver
    spec_grid: compliance.SpectrumObserver


def _observers_init(bank: compliance.SpectrumBank) -> _Observers:
    return _Observers(
        ramp_rack=compliance.ramp_observer_init(),
        ramp_grid=compliance.ramp_observer_init(),
        spec_rack=compliance.spectrum_observer_init(bank),
        spec_grid=compliance.spectrum_observer_init(bank),
    )


def _observers_update(
    obs: _Observers, bank: compliance.SpectrumBank, ch: pdu.CampusChunk, dt: float
) -> _Observers:
    with _prof.scope("observers"):
        return _Observers(
            ramp_rack=compliance.ramp_observer_update(obs.ramp_rack, ch.campus_rack, dt),
            ramp_grid=compliance.ramp_observer_update(obs.ramp_grid, ch.campus_grid, dt),
            spec_rack=compliance.spectrum_observer_update(bank, obs.spec_rack, ch.campus_rack),
            spec_grid=compliance.spectrum_observer_update(bank, obs.spec_grid, ch.campus_grid),
        )


def _make_bank(
    grid_spec: compliance.GridSpec, cfg: pdu.PDUConfig, n_total: int
) -> compliance.SpectrumBank:
    return compliance.make_bank(
        n_total, cfg.sample_dt, float(np.asarray(grid_spec.f_c))
    )


# The engines close their jitted programs over a concrete PDUConfig
# (pdu.condition bakes config scalars into the kernel via float(...)), so
# the jit wrapper must be cached *outside* the engine call or every
# invocation would retrace and recompile from scratch.  PDUConfig
# leaves are config scalars, so a value-based key is exact; anything
# non-scalar falls back to an uncached (per-call) jit.
_ENGINE_CACHE: dict = {}


def _cfg_cache_key(cfg) -> tuple | None:
    try:
        leaves, treedef = jax.tree_util.tree_flatten(cfg)
        return treedef, tuple(np.asarray(leaf).item() for leaf in leaves)
    except (TypeError, ValueError):  # non-scalar or non-hashable leaf
        return None


def _engine_key(cfg, *rest) -> tuple | None:
    cfg_key = _cfg_cache_key(cfg)
    return None if cfg_key is None else (cfg_key,) + rest


def _cached_engine(key, build):
    if key is None:  # un-keyable config: fall back to a per-call jit
        return build()
    fn = _ENGINE_CACHE.get(key)
    if fn is None:
        fn = _ENGINE_CACHE[key] = build()
    return fn


def make_condition_step(cfg: pdu.PDUConfig, *, qp_iters: int = 30, donate: bool = True):
    """A cached, jitted ``(state, trace) -> (grid, state, telemetry)`` step.

    For callers (e.g. ``power.integration.PowerSim``) that walk a stream
    of same-shaped chunks themselves: the returned function is cached per
    config, so repeated construction never retraces, and the carried
    ``PDUState`` is donated between chunks.
    """

    def build():
        def step(st, tr):
            return pdu.condition(cfg, st, tr, qp_iters=qp_iters)

        return jax.jit(step, donate_argnums=(0,) if donate else ())

    return _cached_engine(_engine_key(cfg, "condition_step", qp_iters, donate), build)


def _spectrum_norm(bank: compliance.SpectrumBank):
    """A Hann bank's ``compliance.hann_norm`` as a host scalar, computed op
    by op as an eager report computes it, once per bank length: traced
    into a program, the window's ``cos`` and mean fold at compile time to
    another ulp.  ``None`` where the report needs none (no lines, or a bank
    that normalizes by the samples seen)."""
    if bank.window != "hann" or not bank.bins:
        return None

    def build():
        with jax.ensure_compile_time_eval():
            return np.asarray(compliance.hann_norm(bank.modulus))

    return _cached_engine(("spectrum_norm", bank.modulus), build)


def _report_program(bank: compliance.SpectrumBank):
    """``compliance.report_from_observers`` for one stream, as one cached
    program ``(spec, ramp, spectrum observer, norm) -> report``: what
    ``ConditioningResult.report`` runs, and what the finish traces twice."""
    def build():
        return jax.jit(
            lambda spec, ramp, sob, norm: compliance.report_from_observers(
                spec, ramp, bank, sob, norm))

    return _cached_engine(("report", bank), build)


def _finish_program(cfg, bank, t_total, n_ctrl):
    """The finish's device work as one cached program ``(spec, norm,
    observers, health params, ESS params, health state, aggregates) ->
    (cut aggregates, rack report, grid report, wear report)``: the window
    cuts of the engine's padded outputs, both compliance reports and the
    wear report.  It runs where its committed inputs are (a region's
    campus on its own chip).  The wear and ESS parameters come in as
    arguments: closed over, they would be constants, and XLA turns a
    division by a constant into a multiply.  Against the eager reports
    only XLA's fused multiply-adds move a few fields, by a rounding each
    (``tests/test_fleet_finish.py``)."""
    report = _report_program(bank)

    def build():
        @jax.jit
        def finish(spec, norm, obs, hp, ep, health, campus_rack, campus_grid,
                   soc_mean, ess_frac):
            if t_total is not None:
                campus_rack, campus_grid = (
                    campus_rack[:t_total], campus_grid[:t_total])
            if n_ctrl is not None:
                soc_mean, ess_frac = soc_mean[:n_ctrl], ess_frac[:n_ctrl]
            return (
                campus_rack, campus_grid, soc_mean, ess_frac,
                report(spec, obs.ramp_rack, obs.spec_rack, norm),
                report(spec, obs.ramp_grid, obs.spec_grid, norm),
                hlt.report(hp, ep, health, cfg.sample_dt),
            )

        return finish

    return _cached_engine(
        _engine_key(cfg, "finish", bank, t_total, n_ctrl), build)


def _finish_streaming(
    cfg, grid_spec, state, campus_rack, campus_grid, soc_mean, worst,
    bank, obs, health_trace, ess_frac=None, sm_trace=None, *,
    t_total=None, n_ctrl=None,
):
    """Assemble the result from streaming state: the compliance reports
    come from the cross-chunk observers (exact ramp, Goertzel spec lines),
    not from re-analyzing the materialized campus arrays — the arrays are
    returned for plotting/diagnostics but no longer gate compliance.  The
    observers (and their bank/spec) ride along so ``.report()`` can
    re-derive reports later.  ``t_total`` / ``n_ctrl`` cut the engine's
    padded sample / interval outputs to the stream's length.  The device
    work is one program (``_finish_program``)."""
    with _prof.span("finish"):
        finish = _finish_program(cfg, bank, t_total, n_ctrl)
        (campus_rack, campus_grid, soc_mean, ess_frac, report_rack,
         report_grid, health) = finish(
            grid_spec, _spectrum_norm(bank), obs, _health_params(cfg),
            cfg.ess_params, state.health, campus_rack, campus_grid, soc_mean,
            ess_frac)
        return ConditioningResult(
            campus_rack=campus_rack,
            campus_grid=campus_grid,
            soc_mean=soc_mean,
            report_rack=report_rack,
            report_grid=report_grid,
            state=state,
            max_qp_residual=worst,
            health_trace=health_trace,
            health=health,
            ess_online_frac=ess_frac,
            safemode_trace=sm_trace,
            grid_spec=grid_spec,
            bank=bank,
            observers=obs,
        )


def _condition_chunk(cfg, scen, st, t0, n, *, qp_iters, prep=None):
    """Render + condition one ``n``-sample chunk at absolute sample ``t0``.

    The per-chunk building block of ``_scan_chunks``.  With a fault
    schedule attached to the scenario (and a degraded-mode config) the
    schedule itself is handed to ``pdu.condition`` together with the
    chunk's absolute start sample: availability is rendered *inside* the
    conditioning scan from the episode boundary tables (the degraded fast
    path; safe-mode configs fall back to the streamed derivation
    internally).  Every rendered quantity is pure in the absolute sample
    index (like the trace renderer), so the result is chunk- and
    resume-invariant by construction.  ``prep`` post-processes the rendered
    ``(n, R)`` block (e.g. an in-jit rack sharding constraint).
    """
    from repro.power import scenario as SC

    # Trace-time structural check: the caller's jit retraces automatically
    # when the scenario gains/loses a fault schedule (treedef change).
    faulty = cfg.degraded_mode and scen.faults is not None
    with _prof.scope("render"):
        tr = SC.render(scen, t0, n)
        if tr.ndim == 1:  # unbatched scenario: lift to a 1-rack fleet
            tr = tr[:, None]
        if prep is not None:
            tr = prep(tr)
    return pdu.condition_campus(
        cfg, st, tr, qp_iters=qp_iters,
        faults=scen.faults if faulty else None,
        chunk_start=t0,
        fault_edge=scen.edge_width if faulty else 1,
    )


def _cat(xs):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs)


def _scan_chunks(cfg, scen, st, start, bank, *, chunk, n_full, rem, qp_iters,
                 prep=None, poi=None):
    """The chunk scan of one campus, traced inside the campus engine's jit
    (``_scanned_engine``) and, per campus, the region engine's.

    ``jax.lax.scan`` walks the ``n_full`` full chunks from absolute sample
    ``start`` (a traced scalar): each renders and conditions its
    ``(chunk, R)`` block (``_condition_chunk``) and folds the campus
    compliance observers.  A ``rem``-sample ragged tail runs once more
    after the scan at its *natural* length (``pdu.condition`` ZOH-pads the
    trailing partial controller interval internally, exactly as a one-shot
    whole-trace call would), so the state, ``soc_mean`` and the worst QP
    residual never see pad intervals and are chunk-size invariant.

    ``poi`` is a grid region's own per-chunk step, a pair ``(init, fold)``:
    ``init()`` gives its extra scan carry, and ``fold(carry, ch)`` returns
    the next carry and a tuple of the chunk's per-sample traces.  Returns
    ``(state, CampusChunk, observers, poi carry, traces)``: the chunk's
    fields joined over the window (health and safe-mode rows one per
    chunk), and each ``fold`` trace as its list of full-scan and tail
    pieces, for the caller to join.
    """
    init, fold = poi if poi is not None else (lambda: (), lambda c, ch: (c, ()))
    obs = _observers_init(bank)
    aux = init()

    def step(st, obs, aux, t0, n):
        st2, ch = _condition_chunk(
            cfg, scen, st, t0, n, qp_iters=qp_iters, prep=prep)
        obs2 = _observers_update(obs, bank, ch, cfg.sample_dt)
        aux2, ys = fold(aux, ch)
        return st2, obs2, aux2, ch, ys

    parts, ys_parts, worst, htrace, strace = [], [], [], [], []
    if n_full:
        def body(carry, c_idx):
            st2, obs2, aux2, ch, ys = step(*carry, start + c_idx * chunk, chunk)
            return (st2, obs2, aux2), (ch, ys)

        (st, obs, aux), (ch, ys) = jax.lax.scan(
            body, (st, obs, aux), jnp.arange(n_full, dtype=jnp.int32))
        parts.append(pdu.CampusChunk(
            ch.campus_rack.reshape(-1), ch.campus_grid.reshape(-1),
            ch.soc_mean.reshape(-1), None, None,
            ch.ess_online_frac.reshape(-1),
        ))
        ys_parts.append(tuple(y.reshape(-1) for y in ys))
        worst.append(jnp.max(ch.max_qp_residual))
        htrace.append(ch.health)  # (n_full, 3)
        strace.append(ch.safemode)  # (n_full, 6)
    if rem:
        st, obs, aux, ch, ys = step(st, obs, aux, start + n_full * chunk, rem)
        parts.append(ch)
        ys_parts.append(ys)
        worst.append(ch.max_qp_residual)
        htrace.append(ch.health[None])  # (1, 3)
        strace.append(ch.safemode[None])  # (1, 6)
    camp = pdu.CampusChunk(
        campus_rack=_cat([p.campus_rack for p in parts]),
        campus_grid=_cat([p.campus_grid for p in parts]),
        soc_mean=_cat([p.soc_mean for p in parts]),
        max_qp_residual=functools.reduce(jnp.maximum, worst),
        health=_cat(htrace),
        ess_online_frac=_cat([p.ess_online_frac for p in parts]),
        safemode=_cat(strace),
    )
    return st, camp, obs, aux, [list(t) for t in zip(*ys_parts)]


def _scanned_engine(cfg, qp_iters, chunk, k, n_full, rem, mesh, rack_axis, bank):
    """Cached jitted scanned engine: the whole window in ONE dispatch.

    One campus's ``_scan_chunks``, with the rack axis constrained to
    ``mesh`` in-jit when one is given.  The scenario and the start sample
    ride in as traced arguments, so one compilation serves every scenario
    with the same structure and rack count — and every resume point with
    the same remaining chunk geometry (e.g. fixed-size windows of a long
    stream).  ``scen.faults is None`` vs a schedule changes the scenario
    treedef, which retraces the cached jit automatically.
    """
    def prep(tr):
        if mesh is not None:
            tr = shard_racks_in_jit(tr, mesh, rack_axis)
        return tr

    def build():
        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(scen, st, start):
            st, camp, obs, _, _ = _scan_chunks(
                cfg, scen, st, start, bank, chunk=chunk, n_full=n_full,
                rem=rem, qp_iters=qp_iters, prep=prep)
            return st, camp, obs

        return run

    return _cached_engine(
        _engine_key(cfg, "scanned", qp_iters, chunk, k, n_full, rem,
                    mesh, rack_axis, bank),
        build,
    )


def _chunk_geometry(cfg, target, chunk_intervals, start, stop):
    """The streaming window ``[start, stop)`` of a scenario or region, cut
    into ``chunk``-sample chunks of ``chunk_intervals`` controller
    intervals (``k`` samples each): ``(k, chunk, start, stop, t_total,
    n_full, rem, n_ctrl)``.  ``start`` must sit on an interval boundary so
    a resumed state stays interval-aligned."""
    k = max(int(round(float(cfg.controller.dt) / cfg.sample_dt)), 1)
    chunk = max(int(chunk_intervals), 1) * k
    total = target.total_samples
    stop = total if stop is None else int(stop)
    start = int(start)
    if not 0 <= stop <= total:
        raise ValueError(
            f"stop_sample {stop} outside the scenario ({total} samples)")
    if start < 0 or start % k:
        raise ValueError(
            f"start_sample {start} must be a non-negative multiple of the "
            f"controller interval ({k} samples) so the resumed state stays "
            "interval-aligned")
    t_total = stop - start
    if t_total <= 0:
        raise ValueError(
            f"start_sample {start} is past the scenario end "
            f"(stop at {stop} samples)")
    n_full, rem = divmod(t_total, chunk)
    n_ctrl = -(-t_total // k)
    return k, chunk, start, stop, t_total, n_full, rem, n_ctrl


def _fresh_state(cfg, scen, start, soc0):
    """The state a stream starts from at sample ``start`` of ``scen`` (an
    unbatched scenario lifts to one rack, as the engines do)."""
    from repro.power import scenario as SC

    r0 = SC.render(scen, start, 1)[0]
    if r0.ndim == 0:
        r0 = r0[None]
    return pdu.init_state(cfg, r0, soc0=soc0)


def _condition_scanned_impl(
    cfg: pdu.PDUConfig,
    scenario,
    grid_spec: compliance.GridSpec,
    *,
    soc0: float = 0.5,
    qp_iters: int = 30,
    chunk_intervals: int = 16,
    mesh: jax.sharding.Mesh | None = None,
    rack_axis: str = "data",
    state: pdu.PDUState | None = None,
    start_sample: int = 0,
    stop_sample: int | None = None,
) -> ConditioningResult:
    """Device-resident streaming: render + condition in one scanned jit.

    Because ``scenario.render(s, t0, n)`` is pure in the absolute sample
    index, the render runs *inside* the engine: one ``jax.lax.scan`` over
    chunk indices synthesizes each (chunk, R) block on-device, conditions
    it, and stacks the campus aggregates — one dispatch for the whole
    window, donated ``PDUState``, and rack sharding expressed as a
    ``with_sharding_constraint`` inside the jit.  At equal ``qp_iters`` the
    result matches the one-shot engine (the warm ADMM state rides across
    chunks in ``PDUState.qp_warm``).

    ``state`` + ``start_sample`` / ``stop_sample`` window the stream: pass
    a previous call's returned state and the absolute sample index to
    resume at (a multiple of the controller interval — any multiple of the
    chunk size qualifies); aggregates cover ``[start_sample, stop_sample)``
    of the *unmodified* scenario, so a split-and-resume run reproduces the
    one-call run (truncating ``total_samples`` instead would change the
    edge-smoothing windows near the cut).  A caller-supplied ``state`` is
    copied before the (donated) engine consumes it, so the same checkpoint
    can seed several continuations.
    """
    with _prof.span("prepare"):
        _check_scenario_rate(scenario, cfg)
        _check_scenario_faults(scenario, cfg)
        k, chunk, start, stop, t_total, n_full, rem, n_ctrl = _chunk_geometry(
            cfg, scenario, chunk_intervals, start_sample, stop_sample)
        if state is None:
            state = _fresh_state(cfg, scenario, start, soc0)
        else:
            # The engine donates its state argument; copy so the caller's
            # checkpoint survives (and can seed several continuations).
            state = _copy_tree(state)

        bank = _make_bank(grid_spec, cfg, t_total)
        run = _scanned_engine(
            cfg, qp_iters, chunk, k, n_full, rem, mesh, rack_axis, bank
        )
    with _prof.span("engine"):
        state_f, ch, obs = run(scenario, state, jnp.asarray(start, jnp.int32))
    return _finish_streaming(
        cfg, grid_spec, state_f, ch.campus_rack, ch.campus_grid,
        ch.soc_mean, ch.max_qp_residual, bank, obs, ch.health,
        ch.ess_online_frac, ch.safemode, t_total=t_total, n_ctrl=n_ctrl,
    )


@jax.jit
def _copy_tree(tree):
    """A tree's leaves copied into fresh buffers, in one program."""
    return jax.tree_util.tree_map(jnp.copy, tree)


def _check_scenario_rate(scenario, cfg: pdu.PDUConfig) -> None:
    if abs(1.0 / scenario.sample_hz - cfg.sample_dt) > 1e-9:
        raise ValueError(
            f"scenario sample rate {scenario.sample_hz} Hz != PDU sample_dt "
            f"{cfg.sample_dt} s; build the PDU with sample_dt=1/sample_hz"
        )


def _check_scenario_faults(scenario, cfg: pdu.PDUConfig) -> None:
    if getattr(scenario, "faults", None) is not None and not cfg.degraded_mode:
        raise ValueError(
            "the scenario has a fault schedule attached; conditioning it "
            "requires a degraded-mode config (make_pdu(..., "
            "degraded_mode=True)) so ESS trips are masked and sensor-dropout "
            "NaN samples are bridged instead of poisoning the state"
        )


def _scenario_fault_data(cfg: pdu.PDUConfig, scenario) -> dict:
    """Precomputed availability mask/weight for the one-shot engine, which
    takes them as data — the same pure functions the scanned engine renders
    from the episode tables, so both engines stay bitwise identical under
    any fault schedule."""
    if not (cfg.degraded_mode and getattr(scenario, "faults", None) is not None):
        return {}
    from repro.power import faults as FLT

    k = max(int(round(float(cfg.controller.dt) / cfg.sample_dt)), 1)
    n_ctrl = -(-scenario.total_samples // k)
    return {
        "ess_online": FLT.interval_online(scenario.faults, 0, n_ctrl, k),
        "ess_weight": FLT.ess_weight(
            scenario.faults, 0, scenario.total_samples, scenario.edge_width
        ),
    }


# ------------------------------------------------------------------- facade


@dataclasses.dataclass(frozen=True)
class StreamOptions:
    """Streaming window options for the ``condition`` facade.

    ``chunk_intervals`` sizes the streaming chunk (controller intervals per
    chunk); ``state`` resumes a previous stream (a prior result's
    ``.state`` — a tuple of per-campus states for a grid region);
    ``start_sample`` / ``stop_sample`` window the scanned engine over
    ``[start, stop)`` of the unmodified scenario.
    """

    chunk_intervals: int = 16
    state: object = None
    start_sample: int = 0
    stop_sample: int | None = None


def _as_stream_options(stream) -> StreamOptions:
    if stream is None:
        return StreamOptions()
    if isinstance(stream, StreamOptions):
        return stream
    if isinstance(stream, dict):
        return StreamOptions(**stream)
    raise TypeError(
        f"stream must be a StreamOptions, dict or None, got {type(stream)!r}")


def _reject_stream_options(so: StreamOptions) -> None:
    """The one-shot engine conditions the whole trace from a fresh state."""
    defaults = StreamOptions()
    for f in ("state", "start_sample", "stop_sample"):
        if getattr(so, f) != getattr(defaults, f):
            raise ValueError(
                f"stream option {f!r} is not supported by the 'oneshot' engine")


def _unsupported(what: str) -> ValueError:
    return ValueError(
        f"{what}: the engines are 'scanned' (a Scenario or GridRegion) and "
        "'oneshot' (a Scenario or a materialized (T, R) array); render a "
        "Scenario, or pass the (T, R) trace array to engine='oneshot'")


def condition(
    target,
    cfg: pdu.PDUConfig,
    grid_spec: compliance.GridSpec | None = None,
    *,
    engine: str = "scanned",
    mesh: jax.sharding.Mesh | None = None,
    rack_axis: str = "data",
    stream: StreamOptions | dict | None = None,
    **kwargs,
) -> ConditioningResult:
    """THE conditioning entry point: one facade over both engines.

    ``target`` selects the workload form:

    * a ``power.scenario.Scenario`` — a (possibly heterogeneous, faulted)
      campus, rendered on-device;
    * a ``core.grid.GridRegion`` — N campuses aggregated at a point of
      interconnection (POI observers + mode-band verdicts ride along; with
      a ``mesh`` carrying a ``"campus"`` axis the campuses run in parallel
      under ``shard_map``, bitwise against the sequential loop);
    * a materialized ``(T, R)`` rack-trace array (``engine="oneshot"``).

    ``engine`` picks the execution strategy: ``"scanned"`` (default —
    render + condition in one scanned jit; scenarios/regions only) or
    ``"oneshot"`` (whole-trace ``(T, R)`` materialization, the plain
    reference; supports ``use_plan=False``).  ``mesh`` is taken once
    here — rack sharding (``"data"`` axis) and campus sharding
    (``"campus"`` axis) both derive from it.  ``stream`` bundles the
    windowing/resume options (see ``StreamOptions``).  Remaining keywords
    (``soc0``, ``qp_iters``, ``use_plan``, ``ess_online``, ``ess_weight``)
    pass through to the engine.

    Returns a ``ConditioningResult`` whatever the path; fields the engine
    does not track are ``None``.
    """
    with _prof.span("condition"):
        spec = compliance.GridSpec.create() if grid_spec is None else grid_spec
        so = _as_stream_options(stream)

        if hasattr(target, "campuses"):  # GridRegion (duck-typed; grid imports us)
            from repro.core import grid as _grid

            if engine != "scanned":
                raise ValueError(
                    f"grid regions run the scanned engine only (got {engine!r})")
            return _grid.condition_region(
                cfg, target, spec, mesh=mesh,
                chunk_intervals=so.chunk_intervals, states=so.state,
                start_sample=so.start_sample, stop_sample=so.stop_sample,
                **kwargs,
            )

        if engine not in ("scanned", "oneshot"):
            raise _unsupported(f"unknown engine {engine!r}")
        if callable(target):
            raise _unsupported("a chunk provider is not a conditioning target")
        is_scenario = hasattr(target, "total_samples")
        if engine == "scanned":
            if not is_scenario:
                raise _unsupported(
                    "engine='scanned' renders a Scenario or GridRegion in-jit")
            return _condition_scanned_impl(
                cfg, target, spec, mesh=mesh, rack_axis=rack_axis,
                chunk_intervals=so.chunk_intervals, state=so.state,
                start_sample=so.start_sample, stop_sample=so.stop_sample,
                **kwargs,
            )

        _reject_stream_options(so)
        if is_scenario:
            from repro.power import scenario as SC

            _check_scenario_rate(target, cfg)
            _check_scenario_faults(target, cfg)
            for key, val in _scenario_fault_data(cfg, target).items():
                kwargs.setdefault(key, val)
            target = SC.render(target, 0, target.total_samples)
            if target.ndim == 1:
                target = target[:, None]
        return _condition_oneshot_impl(cfg, target, spec, **kwargs)
