"""Batched controller-QP ADMM iteration loop as one Pallas TPU kernel.

``controller.solve_qp_admm_plan`` runs a fixed number of OSQP-style ADMM
iterations whose per-iteration work is two small precomputed-``K^-1``
GEMMs plus the z-projection and y dual update — at fleet width each
iteration round-trips the (2h, R) / (3h, R) iterates through HBM.  This
kernel runs the whole loop with x, z, y resident in VMEM: the x-update is
the single stacked ``(2h, 5h) @ (5h, r_blk)`` MXU product of
``[sigma K^-1 | K^-1 A']`` against ``[x; rho z - y]``, and ``A x``
exploits the plan's structure ``A = [I; G]`` (box rows of ``A x`` are
``x`` itself, exactly), so only the (h, 2h) SoC block multiplies.

Racks tile across lanes (grid = rack tiles); the plan matrices are a few
KB and ride along each tile.  Matches ``ref.admm_iterate`` (the jnp
fallback) to GEMM rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _admm_kernel(
    ks_ref, g_ref, kq_ref, lo_ref, hi_ref, x0_ref, z0_ref, y0_ref,
    x_ref, z_ref, y_ref,
    *,
    rho: float,
    iters: int,
):
    ks = ks_ref[...]  # (2h, 5h)
    g = g_ref[...]  # (h, 2h)
    kq = kq_ref[...]
    lo = lo_ref[...]
    hi = hi_ref[...]

    # f32 products at full precision: the MXU's default pass count is a
    # backend choice, and the reference pins HIGHEST too.
    dot = functools.partial(
        jnp.dot, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    def body(_, carry):
        x, z, y = carry
        rhs = jnp.concatenate([x, rho * z - y], axis=0)  # (5h, r)
        x_new = dot(ks, rhs) - kq
        ax = jnp.concatenate([x_new, dot(g, x_new)], axis=0)
        # y / rho, not y * (1/rho): the reciprocal multiply is a different
        # rounding and ADMM clip boundaries amplify the ulp over the loop.
        z_new = jnp.clip(ax + y / rho, lo, hi)
        y_new = y + rho * (ax - z_new)
        return (x_new, z_new, y_new)

    x, z, y = jax.lax.fori_loop(
        0, iters, body, (x0_ref[...], z0_ref[...], y0_ref[...])
    )
    x_ref[...] = x
    z_ref[...] = z
    y_ref[...] = y


@functools.partial(jax.jit, static_argnames=("rho", "iters", "r_blk", "interpret"))
def admm_iterate(
    kkt_stack: jax.Array,  # (2h, 5h)
    g_blk: jax.Array,  # (h, 2h)
    kq: jax.Array,  # (2h, R)
    lo: jax.Array,  # (3h, R)
    hi: jax.Array,
    x0: jax.Array,
    z0: jax.Array,
    y0: jax.Array,
    *,
    rho: float,
    iters: int,
    r_blk: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run ``iters`` fused ADMM steps; returns final ``(x, z, y)``."""
    n2, r = kq.shape
    n3 = lo.shape[0]
    r_blk = min(r_blk, max(-(-r // 128) * 128, 128))
    r_pad = -r % r_blk
    f32 = jnp.float32

    def pad(x):
        x = x.astype(f32)
        return jnp.pad(x, ((0, 0), (0, r_pad))) if r_pad else x

    row_spec = lambda n: pl.BlockSpec(n.shape, lambda i: (0, 0))
    batched = [pad(kq), pad(lo), pad(hi), pad(x0), pad(z0), pad(y0)]
    x, z, y = pl.pallas_call(
        functools.partial(_admm_kernel, rho=float(rho), iters=int(iters)),
        grid=((r + r_pad) // r_blk,),
        in_specs=[
            row_spec(kkt_stack),
            row_spec(g_blk),
            pl.BlockSpec((n2, r_blk), lambda i: (0, i)),
            pl.BlockSpec((n3, r_blk), lambda i: (0, i)),
            pl.BlockSpec((n3, r_blk), lambda i: (0, i)),
            pl.BlockSpec((n2, r_blk), lambda i: (0, i)),
            pl.BlockSpec((n3, r_blk), lambda i: (0, i)),
            pl.BlockSpec((n3, r_blk), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((n2, r_blk), lambda i: (0, i)),
            pl.BlockSpec((n3, r_blk), lambda i: (0, i)),
            pl.BlockSpec((n3, r_blk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n2, r + r_pad), f32),
            jax.ShapeDtypeStruct((n3, r + r_pad), f32),
            jax.ShapeDtypeStruct((n3, r + r_pad), f32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(kkt_stack.astype(f32), g_blk.astype(f32), *batched)
    return x[:, :r], z[:, :r], y[:, :r]
