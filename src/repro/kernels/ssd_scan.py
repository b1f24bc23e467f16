"""Pallas TPU kernel: Mamba2 SSD chunked scan (state-space duality).

The SSD insight: a chunked selective-state-space scan decomposes into
MXU-friendly matmuls (intra-chunk quadratic part + low-rank state carry),
with the recurrence surviving only at chunk granularity.  The chunk
recurrence is carried in a VMEM scratch state (N, P) that persists across
grid steps — the grid's chunk axis is sequential ("arbitrary"), the head
axis parallel.

Used by the mamba2-130m and zamba2-7b architectures; it is the compute
hot-spot that makes `long_500k` sub-quadratic.

All intra-chunk math in fp32 on (Q, .) tiles:
  l_t    = A_h * cumsum(dt)[t]                 (log cumulative decay)
  y[t]   = sum_{s<=t} (C_t . B_s) dt_s e^{l_t - l_s} x_s   (intra, matmuls)
         + (C_t e^{l_t}) @ S_prev                          (state carry)
  S_new  = e^{l_Q} S_prev + sum_s dt_s e^{l_Q - l_s} B_s x_s^T

The log decay ``l`` is precomputed outside the kernel (``ref.chunk_decay``)
and streamed in per chunk: computed in-kernel it is exposed to
fusion-context-dependent FP contraction, which broke bit-exact agreement
with the chunked jnp path at small chunk sizes (see chunk_decay's docstring).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import chunk_decay

DEFAULT_CHUNK = 128


def _last_as_col(row, n: int):
    """The last entry of a (1, Q) row repeated as an (n, 1) column.

    A masked lane sum with one nonzero term, so the value is exact:
    Mosaic cannot broadcast a (1, 1) slice along sublanes and lanes at
    once."""
    Q = row.shape[1]
    last = jax.lax.broadcasted_iota(jnp.int32, (n, Q), 1) == Q - 1
    return jnp.sum(jnp.where(last, row, 0.0), axis=1, keepdims=True)


def _kernel(x_ref, dt_row_ref, dt_col_ref, l_row_ref, l_col_ref, b_ref,
            c_ref, y_ref, s_ref, *, nc: int):
    cid = pl.program_id(1)

    @pl.when(cid == 0)
    def _reset():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0].astype(jnp.float32)            # (Q, P)
    # dt and the log decay l arrive as rows (1, Q) and as columns (Q, 1):
    # Mosaic tiles the last two dims of a block, so they cannot be (1, Q)
    # blocks of (H, L) arrays, and the two views spare an in-kernel
    # lane-to-sublane transpose
    dt_row = dt_row_ref[0].astype(jnp.float32)  # (1, Q)
    dt_col = dt_col_ref[0].astype(jnp.float32)  # (Q, 1)
    l_row = l_row_ref[0].astype(jnp.float32)    # (1, Q) log cumulative decay
    l_col = l_col_ref[0].astype(jnp.float32)    # (Q, 1)
    B = b_ref[...].astype(jnp.float32)          # (Q, N)
    C = c_ref[...].astype(jnp.float32)          # (Q, N)
    Q = x.shape[0]

    # intra-chunk quadratic term: M[t,s] = (C_t.B_s) dt_s e^{l_t-l_s} [t>=s]
    CB = jnp.dot(C, B.T, preferred_element_type=jnp.float32)     # (Q, Q)
    # clamped: only t>=s used (l_t-l_s <= 0); keeps masked region finite
    ratio = jnp.exp(jnp.minimum(l_col - l_row, 0.0))            # e^{l_t-l_s}
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    M = jnp.where(t_idx >= s_idx, CB * ratio * dt_row, 0.0)
    y = jnp.dot(M, x, preferred_element_type=jnp.float32)        # (Q, P)

    # inter-chunk carry: y += (C ∘ e^{l_t}) @ S_prev
    S_prev = s_ref[...]                                          # (N, P)
    y = y + jnp.dot(C * jnp.exp(l_col), S_prev, preferred_element_type=jnp.float32)

    # state update: S = e^{l_Q} S_prev + (B ∘ dt e^{l_Q - l_s})^T @ x
    N = S_prev.shape[0]
    w = dt_col * jnp.exp(_last_as_col(l_row, Q) - l_col)         # (Q, 1)
    s_ref[...] = jnp.exp(_last_as_col(l_row, N)) * S_prev + jnp.dot(
        (B * w).T, x, preferred_element_type=jnp.float32
    )

    y_ref[0] = y


def ssd_scan_pallas(
    x: jax.Array,   # (L, H, P)
    dt: jax.Array,  # (L, H)
    A: jax.Array,   # (H,)
    B: jax.Array,   # (L, N)
    C: jax.Array,   # (L, N)
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    L, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, L)
    if L % Q:
        raise ValueError(f"seq len {L} not divisible by chunk {Q}")
    nc = L // Q

    # head-major layout for the grid; decay hoisted (see module docstring)
    xh = jnp.moveaxis(x, 1, 0)      # (H, L, P)
    dth = jnp.moveaxis(dt, 1, 0)    # (H, L)
    lh = jnp.moveaxis(chunk_decay(dt, A, Q), 1, 0)  # (H, L)
    row_spec = pl.BlockSpec((1, 1, Q), lambda h, c: (h, 0, c))
    col_spec = pl.BlockSpec((1, Q, 1), lambda h, c: (h, c, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, nc=nc),
        grid=(H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda h, c: (h, c, 0)),
            row_spec, col_spec, row_spec, col_spec,
            pl.BlockSpec((Q, N), lambda h, c: (c, 0)),
            pl.BlockSpec((Q, N), lambda h, c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, P), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((H, L, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(xh, dth[:, None, :], dth[:, :, None], lh[:, None, :], lh[:, :, None],
      B, C)
    return jnp.moveaxis(out, 0, 1)  # (L, H, P)
