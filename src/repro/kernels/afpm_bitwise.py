"""Pallas TPU kernel: bit-level AFPM elementwise multiply (VPU datapath).

The paper-faithful datapath (segments, conditional execution, compensation,
3n-bit accumulator — see ``repro.core.afpm``) is pure uint32 bit
manipulation, which maps onto the TPU VPU.  This kernel tiles the operands
through VMEM and runs that datapath per block; it is the building block
for CiM-style elementwise workloads (image blending/masking) and for
emulated-numerics studies at tensor granularity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.afpm import AFPMConfig, afpm_mult_f32


DEFAULT_BLOCK = (256, 256)


def _kernel(x_ref, y_ref, o_ref, *, cfg: AFPMConfig):
    o_ref[...] = afpm_mult_f32(x_ref[...], y_ref[...], cfg)


def afpm_bitwise_pallas(
    x: jax.Array,
    y: jax.Array,
    cfg: AFPMConfig = AFPMConfig(),
    *,
    block=DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Elementwise AFPM multiply of two equal-shape arrays (any rank)."""
    if x.shape != y.shape:
        raise ValueError(f"shape mismatch {x.shape} vs {y.shape}")
    shape = x.shape
    flat = 1
    for s in shape:
        flat *= s
    bm, bn = block
    # reshape to 2-D tile space (pad to block multiple)
    ncols = bn
    nrows = (flat + ncols - 1) // ncols
    pad_rows = (-nrows) % bm
    x2 = jnp.resize(jnp.ravel(x), (nrows * ncols,)).reshape(nrows, ncols)
    y2 = jnp.resize(jnp.ravel(y), (nrows * ncols,)).reshape(nrows, ncols)
    if pad_rows:
        x2 = jnp.pad(x2, ((0, pad_rows), (0, 0)))
        y2 = jnp.pad(y2, ((0, pad_rows), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, cfg=cfg),
        grid=(x2.shape[0] // bm,),
        in_specs=[
            pl.BlockSpec((bm, ncols), lambda i: (i, 0)),
            pl.BlockSpec((bm, ncols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, ncols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
        interpret=interpret,
    )(x2, y2)
    return out.reshape(-1)[:flat].reshape(shape)
