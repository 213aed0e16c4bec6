"""Parameter/activation sharding rules (MaxText-style logical axes).

Leaf *paths* in the param pytree are pattern-matched to logical roles, and
roles map to mesh axes per the parallelism config:

  * FSDP+TP for weights: 2D kernels shard (in_dim -> "data", out_dim ->
    "model") for up-projections and (in -> "model", out -> "data") for
    down/output projections; GSPMD then inserts the per-layer all-gathers
    (FSDP) and the TP collectives automatically.
  * Experts: leading expert dim -> "model" (EP), inner in-dim -> "data".
  * Embeddings: vocab -> "model", d_model -> "data".
  * Scan-stacked params have a leading layer axis -> always unsharded.
  * Vectors (norm scales, biases) replicate.

Divisibility is checked at spec-construction time; any dim that does not
divide its assigned axis falls back to unsharded (correct, just less
distributed) with a note collected for the dry-run report.
"""
from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` unless ``axis_types`` says
    otherwise.  The installed jax defaults to ``Explicit`` axes, under which
    plain indexing of a sharded result (``x[c]`` on a campus-sharded array)
    raises; every mesh in this repo is written for GSPMD's ``Auto`` axes."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axis_names)
    kw: dict[str, Any] = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names), axis_types=tuple(axis_types), **kw
    )


# role -> (axis assignment per tensor dim, counted from the LAST dim)
# (in_axis, out_axis) for 2D kernels.
_UP_KERNELS = (
    "wq", "wk", "wv", "wg", "w_gate", "w_up", "wq_a", "wq_b", "wkv_a",
    "wk_b", "wv_b", "w_in", "wr", "mix_lora_a", "a",
)
_DOWN_KERNELS = ("wo", "w_down", "w_out", "w_concat", "b", "wv_cm")
_REPLICATE = ("scale", "bias", "a_log", "dt_bias", "d_skip", "decay_base",
              "mu_base", "mu_k", "mu_r", "u_bonus", "_sub_heads", "dec_pos")


def _role_of(path: tuple[str, ...], ndim: int) -> str:
    names = [p for p in path]
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    if leaf == "embedding" or (leaf == "kernel" and parent == "lm_head"):
        return "embed"
    if leaf in _REPLICATE or parent in ("conv",):
        return "replicate"
    if parent == "router":
        return "replicate"
    if "experts" in names:
        return "expert"
    if leaf == "kernel":
        if parent in _UP_KERNELS:
            return "up"
        if parent in _DOWN_KERNELS:
            return "down"
        return "replicate"
    if parent in ("mix_lora_b", "decay_lora"):
        return "replicate"
    if leaf in _UP_KERNELS or leaf in _DOWN_KERNELS:
        # raw arrays named like kernels (lora a/b mats)
        return "up" if leaf in _UP_KERNELS else "down"
    return "replicate"


def _fits(dim: int, mesh: Mesh, axis: str | None) -> bool:
    if axis is None:
        return True
    return dim % mesh.shape[axis] == 0


def param_spec(
    path: tuple[str, ...],
    shape: tuple[int, ...],
    mesh: Mesh,
    *,
    data_axis: str = "data",
    model_axis: str = "model",
    notes: list | None = None,
) -> P:
    """PartitionSpec for one parameter leaf."""
    ndim = len(shape)
    role = _role_of(path, ndim)
    none_prefix = (None,) * (ndim - 2)

    def note(msg):
        if notes is not None:
            notes.append(f"{'/'.join(path)}: {msg}")

    if role == "replicate" or ndim == 0:
        return P()
    if role == "embed":
        # (vocab, d) -> vocab on model (always padded to divide), d on data
        v_ax = model_axis if _fits(shape[-2], mesh, model_axis) else None
        d_ax = data_axis if _fits(shape[-1], mesh, data_axis) else None
        if v_ax is None:
            note("vocab dim not divisible; replicated")
        return P(*none_prefix, v_ax, d_ax)
    if role == "expert":
        # (..., E, in, out): E -> model (EP), in -> data (FSDP).
        # NOTE: pure EP over BOTH axes (1 expert/device, zero weight
        # gathers) was tried and REFUTED under GSPMD — the partitioner
        # cannot infer the 256-way token all-to-all from the dispatch
        # reshape and falls back to full rematerialization (~10x more
        # collective bytes, EXPERIMENTS §Perf-3 it.1).  Doing it properly
        # requires explicit shard_map all-to-alls (future work).
        if ndim < 3:
            return P()
        e_ax = model_axis if _fits(shape[-3], mesh, model_axis) else None
        i_ax = data_axis if _fits(shape[-2], mesh, data_axis) else None
        if e_ax is None:
            note("expert dim not divisible; replicated")
        return P(*(None,) * (ndim - 3), e_ax, i_ax, None)
    if ndim == 1:
        return P()
    if role == "up":
        i_ax = data_axis if _fits(shape[-2], mesh, data_axis) else None
        o_ax = model_axis if _fits(shape[-1], mesh, model_axis) else None
        if o_ax is None:
            note("up out-dim not divisible; unsharded")
        return P(*none_prefix, i_ax, o_ax)
    # down
    i_ax = model_axis if _fits(shape[-2], mesh, model_axis) else None
    o_ax = data_axis if _fits(shape[-1], mesh, data_axis) else None
    return P(*none_prefix, i_ax, o_ax)


def param_specs(shapes: Any, mesh: Mesh, **kw) -> Any:
    """PartitionSpec pytree parallel to a ShapeDtypeStruct/array pytree."""
    notes: list[str] = kw.pop("notes", None) or []

    def visit(path, leaf):
        names = tuple(
            k.name if hasattr(k, "name") else str(getattr(k, "key", k)) for k in path
        )
        return param_spec(names, tuple(leaf.shape), mesh, notes=notes, **kw)

    return jax.tree_util.tree_map_with_path(visit, shapes)


def shardings(shapes: Any, mesh: Mesh, **kw) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(shapes, mesh, **kw)
    )


# ------------------------------------------------------------ activations --


def _clean_spec(m: Mesh, spec: tuple, shape: tuple[int, ...]) -> P:
    """Drop spec axes absent from ``m`` or not dividing their dim."""
    names = set(m.axis_names)

    def keep(s, dim):
        if s is None:
            return None
        if isinstance(s, (tuple, list)):
            kept = tuple(a for a in s if a in names)
            if not kept:
                return None
            total = 1
            for a in kept:
                total *= m.shape[a]
            return kept if dim % total == 0 else None
        if s not in names:
            return None
        return s if dim % m.shape[s] == 0 else None

    spec = spec + (None,) * (len(shape) - len(spec))
    return P(*(keep(s, d) for s, d in zip(spec, shape)))


def maybe_constrain(x: jax.Array, *spec) -> jax.Array:
    """``with_sharding_constraint`` that is a no-op without a mesh context.

    Model code calls this at activation boundaries — without it GSPMD can
    "win" by keeping the d_model contraction sharded and the BATCH
    replicated (observed: 16x activation blow-up through attention), and
    the (B, T, V) fp32 logits must shard over vocab on "model" or the loss
    alone is tens of GB per device at the assigned shapes.  Axis names
    absent from the ambient mesh and axes that do not divide their dim are
    dropped, so smoke tests (no mesh), debug meshes, and batch-1 long-
    context shapes run unchanged.
    """
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    if m.empty or m.size == 1:
        return x
    cleaned = _clean_spec(m, spec, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, cleaned))


def constrain_to_mesh(x: jax.Array, mesh: Mesh, *spec) -> jax.Array:
    """``with_sharding_constraint`` against an *explicit* mesh.

    Unlike ``maybe_constrain`` this needs no ambient mesh context, so it
    works inside any jit given a mesh object — the fleet engines use it to
    express rack sharding of streamed chunks *inside* the step instead of
    staging every chunk through a host-side ``device_put``.  The same
    guards apply: a single-device mesh is a no-op, and axes that are
    missing or do not divide their dim are dropped.
    """
    if mesh.empty or mesh.size == 1:
        return x
    cleaned = _clean_spec(mesh, spec, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, cleaned))


def shard_racks(traces: jax.Array, mesh: Mesh, axis: str = "data") -> jax.Array:
    """Place the rack axis of a host-resident (T, R) trace array across a
    mesh axis (``device_put``) so fleet conditioning runs data-parallel
    across devices.  Inside a jit, use ``shard_racks_in_jit`` instead —
    arrays already on device never need the host staging this call forces.

    (Moved here from ``core.fleet``: these are mesh utilities, not fleet
    logic; ``fleet`` re-exports both names for compatibility.)"""
    return jax.device_put(traces, NamedSharding(mesh, P(None, axis)))


def shard_racks_in_jit(
    traces: jax.Array, mesh: Mesh, axis: str = "data"
) -> jax.Array:
    """In-jit variant of ``shard_racks``: expresses the rack sharding as a
    ``with_sharding_constraint`` against an explicit mesh, so streamed
    chunks (rendered or passed as jit arguments) are partitioned by GSPMD
    without a per-chunk host ``device_put`` round-trip."""
    return constrain_to_mesh(traces, mesh, None, axis)


# --------------------------------------------------------------- shard_map --

def shard_map_compat(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check.

    The grid-region engine returns ``psum``-reduced POI aggregates under
    ``out_specs=P()``: they are replicated, but the checker cannot prove it
    through ``lax.scan`` carries.  The mapped body names no auto axes and
    calls no ``with_sharding_constraint``: every axis is manual over the
    campus shards (EXPERIMENTS §Grid-region).
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def region_mesh(
    n_campuses: int,
    *,
    campus_axis: str = "campus",
    rack_axis: str = "data",
    devices=None,
) -> Mesh:
    """2-D (campus, data) mesh over the available devices.

    The campus axis gets exactly ``n_campuses`` shards (one campus per
    shard keeps the in-scan ``psum`` reduction order equal to the
    sequential left-to-right campus sum — the bitwise-parity contract);
    every remaining device folds into the trailing rack/data axis.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if n_campuses <= 0:
        raise ValueError(f"n_campuses must be positive, got {n_campuses}")
    if len(devs) % n_campuses:
        raise ValueError(
            f"{len(devs)} devices do not tile {n_campuses} campuses; pass "
            "an explicit device subset whose size is a campus multiple"
        )
    return make_mesh(
        (n_campuses, len(devs) // n_campuses),
        (campus_axis, rack_axis),
        devices=np.asarray(devs),
    )


def campus_rows(mesh: Mesh) -> np.ndarray:
    """(n_campuses, devices per campus) array of ``mesh``'s devices: row
    ``c`` holds every device that keeps campus ``c``'s shard of an array
    sharded ``P("campus")``, the campus's own device first."""
    devs = np.moveaxis(mesh.devices, mesh.axis_names.index("campus"), 0)
    return devs.reshape(devs.shape[0], -1)


def constrain_activations(x: jax.Array) -> jax.Array:
    """Standard (B, T, D) activation constraint: batch on ("pod","data")."""
    return maybe_constrain(x, ("pod", "data"))


def constrain_gathered_weight(path_names: tuple[str, ...], leaf: jax.Array) -> jax.Array:
    """Re-constrain a parameter leaf to its rules-assigned sharding WITHOUT
    the data (FSDP) axis — i.e. "gather once, keep TP".  Used to amortize
    FSDP all-gathers across microbatches for small weights."""
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    if m.empty or m.size == 1 or "model" not in m.axis_names:
        return leaf
    # The rules-assigned spec with every non-"model" axis dropped: same
    # TP orientation, FSDP axis gathered.
    spec = param_spec(path_names, tuple(leaf.shape), m)
    padded = (tuple(spec) + (None,) * leaf.ndim)[: leaf.ndim]
    cleaned = P(*(s if s == "model" else None for s in padded))
    return jax.lax.with_sharding_constraint(leaf, NamedSharding(m, cleaned))


def batch_spec(mesh: Mesh, batch: int, *, pod: bool = False) -> P:
    """Sharding for (B, T, ...) activations/token batches.

    Batch shards over ("pod","data") when it divides; a batch of 1
    (long-context decode) leaves batch unsharded and relies on
    head/sequence sharding inside the model.
    """
    axes: tuple[str, ...] = ()
    if pod and "pod" in mesh.shape:
        axes = ("pod", "data")
    else:
        axes = ("data",)
    total = int(np.prod([mesh.shape[a] for a in axes]))
    if batch % total == 0:
        return P(axes if len(axes) > 1 else axes[0])
    if batch % mesh.shape["data"] == 0:
        return P("data")
    return P(None)


def decode_state_specs(state_shapes: Any, mesh: Mesh) -> Any:
    """PartitionSpecs for a decode-state pytree (KV caches / SSM states).

    Leaf-name driven: KV ``k``/``v`` (stacked (L, B, S, H, hd) or MLA
    (L, B, S, R)) shard batch on "data" and heads on "model" when they
    divide, else the sequence dim; SSM/RWKV states shard heads/channels on
    "model"; tiny shift/length leaves replicate.  Any non-divisible dim
    falls back to unsharded.
    """
    dp = mesh.shape["data"]
    tp = mesh.shape["model"]

    def fit(dim, ax, n):
        return ax if dim % n == 0 and dim >= n else None

    def visit(path, leaf):
        name = str(getattr(path[-1], "name", getattr(path[-1], "key", path[-1])))
        shp = tuple(leaf.shape)
        nd = len(shp)
        if name in ("length",) or nd <= 1:
            return P()
        if name in ("k", "v", "cross_k", "cross_v"):
            if nd == 5:  # (L, B, S, H, hd)
                b_ax = fit(shp[1], "data", dp)
                h_ax = fit(shp[3], "model", tp)
                s_ax = None if h_ax else fit(shp[2], "model", tp)
                return P(None, b_ax, s_ax, h_ax, None)
            if nd == 4:  # MLA (L, B, S, R)
                b_ax = fit(shp[1], "data", dp)
                s_ax = fit(shp[2], "model", tp)
                return P(None, b_ax, s_ax, None)
            return P()
        if name == "wkv":  # (L, B, H, hd, hd)
            return P(None, fit(shp[1], "data", dp), fit(shp[2], "model", tp), None, None)
        if name == "ssm":  # (G, K, B, H, N, Ph)
            return P(None, None, fit(shp[2], "data", dp), fit(shp[3], "model", tp), None, None)
        if name == "conv":  # (G, K, B, W, CH)
            return P(None, None, fit(shp[2], "data", dp), None, fit(shp[4], "model", tp))
        if name in ("shift_tm", "shift_cm"):  # (L, B, 1, D)
            return P(None, fit(shp[1], "data", dp), None, fit(shp[3], "model", tp))
        # default: try batch-ish second dim
        if nd >= 2:
            return P(None, fit(shp[1], "data", dp), *([None] * (nd - 2)))
        return P()

    return jax.tree_util.tree_map_with_path(visit, state_shapes)


def cache_spec(mesh: Mesh, batch: int, kv_heads_or_none: int | None) -> P:
    """KV cache (B, S, H, D) or MLA (B, S, R): shard batch on data; heads on
    model when divisible, else the sequence dim."""
    b_ax = "data" if batch % mesh.shape["data"] == 0 else None
    if kv_heads_or_none is not None and kv_heads_or_none % mesh.shape["model"] == 0:
        return P(b_ax, None, "model", None)
    if kv_heads_or_none is None:
        return P(b_ax, "model", None)
    return P(b_ax, "model", None, None)
