"""RWKV-6 (Finch) time-mix recurrence Pallas TPU kernel.

The rwkv6-7b architecture's hot loop — and the reason the `long_500k`
cells are tractable at all: the recurrence carries a per-head (D x D)
state with O(T) work instead of O(T^2) attention.

    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

TPU mapping: one (batch*head) per grid row; the (D x D) fp32 state lives in
VMEM scratch across the sequential time-block axis; within a block the
per-token outer products and matvecs run on the VPU/MXU with D = 64 lanes.
The data-dependent decay ``w_t`` makes this inexpressible as a plain
associative matmul scan without materializing (D x D) per token — the
in-VMEM sequential formulation avoids that HBM blow-up entirely (that IS
the TPU adaptation of the CUDA wkv kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sf_ref, state,
    *, block_t: int, t_total: int,
):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state[...] = s0_ref[0]

    u = u_ref[0].astype(jnp.float32)  # (1, D) bonus row

    n_valid = jnp.minimum(block_t, t_total - pl.program_id(1) * block_t)

    def step(t, s):
        r_t = r_ref[0, t, :].astype(jnp.float32)[None, :]  # (1, D)
        k_t = k_ref[0, t, :].astype(jnp.float32)[None, :]
        v_t = v_ref[0, t, :].astype(jnp.float32)[None, :]
        w_t = w_ref[0, t, :].astype(jnp.float32)[None, :]
        kv = k_t.T @ v_t  # (D, D) outer product
        out = r_t @ (s + u.T * kv)  # (1, D)
        o_ref[0, t, :] = out[0].astype(o_ref.dtype)
        return w_t.T * s + kv

    state[...] = jax.lax.fori_loop(0, n_valid, step, state[...])
    sf_ref[0] = state[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_scan(
    r: jax.Array,  # (B, H, T, D)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decay in (0, 1)
    u: jax.Array,  # (H, D)
    state0: jax.Array | None = None,  # (B, H, D, D)
    *,
    block_t: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    b, h, t, d = r.shape
    if state0 is None:
        state0 = jnp.zeros((b, h, d, d), jnp.float32)
    block_t = min(block_t, t)
    pad_t = -t % block_t

    def flat(x):
        x = x.reshape(b * h, t, d)
        if pad_t:
            x = jnp.concatenate([x, jnp.zeros((b * h, pad_t, d), x.dtype)], axis=1)
        return x

    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(w)
    uf = jnp.tile(u[None, :, :], (b, 1, 1)).reshape(b * h, 1, d)
    s0 = state0.reshape(b * h, d, d)
    grid = (b * h, (t + pad_t) // block_t)
    o, sf = pl.pallas_call(
        functools.partial(_rwkv6_kernel, block_t=block_t, t_total=t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_t, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_t, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_t, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, d, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, d, d), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t + pad_t, d), r.dtype),
            jax.ShapeDtypeStruct((b * h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0)
    return o[:, :t].reshape(b, h, t, d), sf.reshape(b, h, d, d)
