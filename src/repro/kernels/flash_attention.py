"""Block-wise online-softmax attention (fwd + bwd) Pallas TPU kernels.

The training stack's compute hot spot.  Standard FlashAttention-style
tiling adapted to TPU: query blocks of ``block_q`` ride the grid with the
KV sequence as the innermost (sequential) axis; the running max / sum /
accumulator live in VMEM scratch.  Causal masking skips fully-masked KV
blocks via ``pl.when`` (no work issued), and only the diagonal blocks pay
for per-element masks.  The forward kernel also emits the per-row
log-sum-exp, which makes the backward a pure recompute: no (Tq, Tk)
probability matrix is ever materialized in HBM.

Backward is the FlashAttention-2 split — one kernel accumulates dK/dV
with the query sequence innermost (sequential), a second accumulates dQ
with the KV sequence innermost — both recomputing ``p = exp(s - lse)``
per tile from VMEM-resident operands.  ``jax.custom_vjp`` wires them in;
``algorithm="reference"`` swaps the backward for the mathematically
identical dense jnp formulation (the test oracle, and the fallback for
shapes the tiles do not divide).

GQA is handled by the wrapper (queries grouped per KV head) *outside*
the custom-vjp boundary, so the head-group reduction of dK/dV falls out
of the ``jnp.repeat`` VJP for free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, block_q: int, block_k: int, seq_k: int, causal: bool, scale: float, q_offset: int,
):
    del seq_k
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    k_steps = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: query global index = q_offset + qi*block_q + row; key index =
    # ki*block_k + col.  Skip blocks with k_start > q_end entirely.
    q_start = q_offset + qi * block_q
    q_end = q_start + block_q - 1
    k_start = ki * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)  # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    if causal:
        pl.when(k_start <= q_end)(_compute)
    else:
        _compute()

    @pl.when(ki == k_steps - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, :] = (m_scr[...] + jnp.log(l))[:, 0]


def _flash_fwd(
    q: jax.Array,  # (BH, Tq, D)
    k: jax.Array,  # (BH, Tk, D)
    v: jax.Array,  # (BH, Tk, D)
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    assert tq % block_q == 0 and tk % block_k == 0, "pad sequences to block size"
    # decode-style offset: query i is at absolute position i + (tk - tq)
    q_offset = tk - tq if causal else 0
    grid = (bh, tq // block_q, tk // block_k)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel,
            block_q=block_q, block_k=block_k, seq_k=tk, causal=causal,
            scale=scale, q_offset=q_offset,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, block_q: int, block_k: int, causal: bool, scale: float, q_offset: int,
):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    q_steps = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = q_offset + qi * block_q
    q_end = q_start + block_q - 1
    k_start = kj * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :]  # (bq,)
        delta = delta_ref[0, :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])  # masked entries: exp(-inf) == 0
        dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk_scr[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    if causal:
        pl.when(q_end >= k_start)(_compute)
    else:
        _compute()

    @pl.when(qi == q_steps - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, block_q: int, block_k: int, causal: bool, scale: float, q_offset: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    k_steps = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = q_offset + qi * block_q
    q_end = q_start + block_q - 1
    k_start = kj * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :]
        delta = delta_ref[0, :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dq_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    if causal:
        pl.when(q_end >= k_start)(_compute)
    else:
        _compute()

    @pl.when(kj == k_steps - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_bwd(
    q, k, v, o, lse, do,
    *, causal, scale, block_q, block_k, interpret,
):
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    q_offset = tk - tq if causal else 0
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    common = dict(causal=causal, scale=scale, q_offset=q_offset)
    row = lambda: pl.BlockSpec((1, block_q), lambda b, i, j: (b, i))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k, **common
        ),
        grid=(bh, tk // block_k, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, block_q), lambda b, j, i: (b, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_q=block_q, block_k=block_k, **common
        ),
        grid=(bh, tq // block_q, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            row(), row(),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _bwd_reference(q, k, v, o, lse, do, *, causal, scale):
    """Dense lse-based backward: the exact math the tiled kernels evaluate
    (p recomputed from the saved log-sum-exp), as one jnp expression."""
    f32 = jnp.float32
    tq, tk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(f32), k.astype(f32)) * scale
    if causal:
        rows = jnp.arange(tq)[:, None] + (tk - tq)
        s = jnp.where(rows >= jnp.arange(tk)[None, :], s, NEG_INF)
    p = jnp.exp(s - lse[..., None])
    dof = do.astype(f32)
    dv = jnp.einsum("bqk,bqd->bkd", p, dof)
    dp = jnp.einsum("bqd,bkd->bqk", dof, v.astype(f32))
    delta = jnp.sum(dof * o.astype(f32), axis=-1)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bqk,bkd->bqd", ds, k.astype(f32)) * scale
    dk = jnp.einsum("bqk,bqd->bkd", ds, q.astype(f32)) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, causal, scale, block_q, block_k, interpret, algorithm):
    out, _ = _flash_fwd(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


def _flash_core_fwd(q, k, v, causal, scale, block_q, block_k, interpret, algorithm):
    out, lse = _flash_fwd(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, scale, block_q, block_k, interpret, algorithm, res, do):
    q, k, v, out, lse = res
    if algorithm == "reference":
        return _bwd_reference(q, k, v, out, lse, do, causal=causal, scale=scale)
    return _flash_bwd(
        q, k, v, out, lse, do,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret", "algorithm"),
)
def flash_attention(
    q: jax.Array,  # (B, H, Tq, D)
    k: jax.Array,  # (B, Hkv, Tk, D)
    v: jax.Array,  # (B, Hkv, Tk, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    algorithm: str = "auto",
) -> jax.Array:
    """Public wrapper: GQA head grouping + flatten to (BH, T, D).

    Differentiable: ``algorithm="auto"`` backs the VJP with the fused
    Pallas dK/dV + dQ kernels; ``"reference"`` uses the dense lse-based
    jnp backward (same math, the test oracle).  The GQA ``jnp.repeat``
    sits outside the custom-vjp boundary, so dK/dV head-group reduction
    is handled by its VJP."""
    b, h, tq, d = q.shape
    hkv = k.shape[1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    groups = h // hkv
    kx = jnp.repeat(k, groups, axis=1).reshape(b * h, -1, d)
    vx = jnp.repeat(v, groups, axis=1).reshape(b * h, -1, d)
    out = _flash_core(
        q.reshape(b * h, tq, d), kx, vx,
        causal, scale, block_q, block_k, interpret, algorithm,
    )
    return out.reshape(b, h, tq, d)
