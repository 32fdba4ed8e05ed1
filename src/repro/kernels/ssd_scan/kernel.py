"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk computation.

The SSD algorithm splits the sequence into chunks; within a chunk the
recurrence is materialized as a masked-decay "attention" (matmul-heavy — MXU
work), while the chunk-to-chunk recurrence is a tiny scan done outside the
kernel.  This kernel computes, per (batch, head, chunk):

    cs      = inclusive cumsum of dA                (via tril-ones matmul —
                                                     Mosaic has no cumsum)
    L       = exp(cs_i - cs_j) lower-triangular
    y_intra = ((C B^T) * L) (x * dt)
    state   = (x*dt*decay_to_end)^T B               (chunk contribution)

Grid (B, H, nc); all operands for one grid cell fit comfortably in VMEM
(Q=256, N=128, P=64 -> ~1 MB of fp32 tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                y_ref, st_ref, cs_ref, *, q: int):
    x = x_ref[0, 0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (Q, 1)
    A = a_ref[0]                                    # scalar
    Bm = b_ref[0, 0, 0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)         # (Q, N)

    dA = dt * A                                     # (Q, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril_inc = (row >= col).astype(jnp.float32)     # inclusive cumsum matrix
    cs = jax.lax.dot_general(tril_inc, dA, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, 1)

    diff = cs - cs.T                                # cs_i - cs_j
    L = jnp.where(row >= col, jnp.exp(diff), 0.0)   # (Q, Q)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    xdt = x * dt                                    # (Q, P)
    y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    decay_end = jnp.exp(cs[-1:, :] - cs)            # (Q, 1)
    xw = xdt * decay_end                            # (Q, P)
    state = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P, N)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0, 0] = state.astype(st_ref.dtype)
    cs_ref[0, 0, 0] = cs.astype(cs_ref.dtype)


def ssd_intra_pallas(x, dt, A, Bm, Cm):
    """x: (B, H, nc, Q, P); dt: (B, H, nc, Q, 1); A: (H,);
    Bm, Cm: (B, G, nc, Q, N).  Returns (y_intra, states, cs)."""
    B, H, nc, Q, P = x.shape
    G, N = Bm.shape[1], Bm.shape[4]
    grid = (B, H, nc)
    kern = functools.partial(_ssd_kernel, q=Q)
    bc_map = lambda b, h, c: (b, h * G // H, c, 0, 0)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, 1, Q, N), bc_map),
            pl.BlockSpec((1, 1, 1, Q, N), bc_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, P, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nc, P, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nc, Q, 1), jnp.float32),
        ],
        interpret=kernels.interpret_mode(),
    )(x, dt, A, Bm, Cm)
