"""LC input-filter state-space IIR as a Pallas TPU kernel.

Conditioning hours of kHz-rate traces for thousands of racks is the power
layer's compute hot spot: a 1-hour fleet simulation at 1 kHz over 10k racks
is 3.6e10 recurrence steps.  The recurrence is sequential in time but
embarrassingly parallel across racks, which maps perfectly onto the TPU
vector unit:

  * racks ride the 128-wide **lane** dimension,
  * time is blocked through VMEM (``block_t`` samples per grid step),
  * the 3-vector filter state lives in a VMEM scratch that persists across
    the sequential grid (dimension_semantics = "arbitrary"),
  * the 3x3 state matrix is unrolled into 9 scalar*vector FMAs per sample
    (no MXU involvement — this is a VPU kernel).

HBM traffic is exactly one read of the node trace + one write of the grid
trace; all state stays resident.  The pure-jnp oracle is ``ref.lc_filter``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lc_kernel(
    ad_ref, bd_ref, x0_ref, u_ref, c_ref, y_ref, xf_ref, state,
    *, block_t: int, t_total: int,
):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        state[...] = x0_ref[...]

    # Last block may be partial: only advance through the valid samples so
    # the final state corresponds to exactly t_total steps.
    n_valid = jnp.minimum(block_t, t_total - pl.program_id(0) * block_t)

    a = ad_ref[...]  # (3, 3)
    b = bd_ref[...]  # (3, 2)
    c = c_ref[...]  # (1, 3)

    def step(t, x):
        # x: (3, R) f32
        u_t = u_ref[t, :]  # (R,)
        y_ref[t, :] = (c[0, 0] * x[0] + c[0, 1] * x[1] + c[0, 2] * x[2]).astype(
            y_ref.dtype
        )
        x0n = a[0, 0] * x[0] + a[0, 1] * x[1] + a[0, 2] * x[2] + b[0, 1] * u_t + b[0, 0]
        x1n = a[1, 0] * x[0] + a[1, 1] * x[1] + a[1, 2] * x[2] + b[1, 1] * u_t + b[1, 0]
        x2n = a[2, 0] * x[0] + a[2, 1] * x[1] + a[2, 2] * x[2] + b[2, 1] * u_t + b[2, 0]
        return jnp.stack([x0n, x1n, x2n], axis=0)

    state[...] = jax.lax.fori_loop(0, n_valid, step, state[...])
    xf_ref[...] = state[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def lc_filter(
    ad: jax.Array,  # (3, 3)
    bd: jax.Array,  # (3, 2)
    c_row: jax.Array,  # (3,)
    x0: jax.Array,  # (R, 3)
    node_power: jax.Array,  # (T, R)
    *,
    block_t: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (grid (T, R), x_final (R, 3)); v_in fixed at 1 per-unit."""
    t, r = node_power.shape
    block_t = min(block_t, t)
    pad_t = -t % block_t
    u = node_power.astype(jnp.float32)
    if pad_t:
        u = jnp.concatenate([u, jnp.tile(u[-1:], (pad_t, 1))], axis=0)
    grid = ((t + pad_t) // block_t,)
    y, xf = pl.pallas_call(
        functools.partial(_lc_kernel, block_t=block_t, t_total=t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((3, 3), lambda i: (0, 0)),
            pl.BlockSpec((3, 2), lambda i: (0, 0)),
            pl.BlockSpec((3, r), lambda i: (0, 0)),
            pl.BlockSpec((block_t, r), lambda i: (i, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, r), lambda i: (i, 0)),
            pl.BlockSpec((3, r), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t + pad_t, r), node_power.dtype),
            jax.ShapeDtypeStruct((3, r), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((3, r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(
        ad.astype(jnp.float32),
        bd.astype(jnp.float32),
        x0.T.astype(jnp.float32),
        u,
        c_row.reshape(1, 3).astype(jnp.float32),
    )
    return y[:t], xf.T
