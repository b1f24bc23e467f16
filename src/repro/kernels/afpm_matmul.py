"""Pallas TPU kernel: segmented approximate matmul (the paper's AFPM on the MXU).

TPU adaptation of mantissa segmentation (DESIGN.md §2): each fp32 operand
tile is split in-VMEM into a high bf16 segment (hidden bit + top 7 mantissa
bits — the "A"/"C" segment) and a low bf16 segment (the "B"/"D" segment).
The mantissa partial products map onto MXU passes:

    AC   = hi(x) @ hi(w)      always executed (dominant term)
    AD   = lo(x) @ hi(w)      pass >= 2
    BC   = hi(x) @ lo(w)      pass >= 3
    BD   = lo(x) @ lo(w)      always omitted  (paper Eq. 6)

``passes`` is the accuracy knob (1 = ACL-like, 3 = AC-n-n-like); the exact
baseline is the fp32 dot (6 equivalent passes).  Accumulation is exact
fp32 in a VMEM scratch accumulator, matching the CiM macro's exact adder
tree.

2-D operands use a (M/bm, N/bn, K/bk) grid with k innermost; batched
(3-D+) operands flatten their leading axes into one grid batch dimension
— (G, M/bm, N/bn, K/bk) — so every batch element tiles the MXU natively
instead of being reshape-flattened into a tall matmul.  The fp32->bf16
split happens per tile in VMEM, so HBM traffic is the fp32 operands read
once — arithmetic intensity is identical to a plain matmul while the MXU
work is 1-3 bf16 passes instead of 6 (fp32 emulation) per tile.

Block sizes default to the substrate's tuning tables via
``kernels/dispatch.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _split(t):
    hi = t.astype(jnp.bfloat16)
    lo = (t - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _accumulate(x, w, acc_ref, *, passes: int):
    x = x.astype(jnp.float32)  # (bm, bk)
    w = w.astype(jnp.float32)  # (bk, bn)
    xh, xl = _split(x)
    wh, wl = _split(w)

    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    acc = dot(xh, wh)                   # AC
    if passes >= 2:
        acc = acc + dot(xl, wh)         # AD (x low bits recovered)
    if passes >= 3:
        acc = acc + dot(xh, wl)         # BC (w low bits recovered)
    acc_ref[...] += acc


def _kernel2d(x_ref, w_ref, o_ref, acc_ref, *, passes: int, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref[...], w_ref[...], acc_ref, passes=passes)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _kernel_batched(x_ref, w_ref, o_ref, acc_ref, *, passes: int, nk: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref[0], w_ref[...], acc_ref, passes=passes)

    @pl.when(pl.program_id(3) == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...]


def _pad2(t, p0, p1):
    return jnp.pad(t, ((0, p0), (0, p1))) if p0 or p1 else t


def afpm_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    passes: int = 3,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Segmented matmul ``x (..., K) @ w (K, N) -> (..., N) fp32``.

    ``x`` may carry any number of leading batch dims; they become a native
    grid axis (the weight tile is shared across it).
    """
    if x.ndim < 2 or w.ndim != 2:
        raise ValueError(f"need x (..., M, K) @ w (K, N); got {x.shape} @ {w.shape}")
    *lead, M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    w = _pad2(w, pk, pn)
    Np = w.shape[1]

    if not lead:
        x = _pad2(x, pm, pk)
        Mp, Kp = x.shape
        nk = Kp // bk
        out = pl.pallas_call(
            functools.partial(_kernel2d, passes=passes, nk=nk),
            grid=(Mp // bm, Np // bn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
        )(x, w)
        return out[:M, :N] if pm or pn else out

    G = 1
    for s in lead:
        G *= s
    x = x.reshape(G, M, K)
    if pm or pk:
        x = jnp.pad(x, ((0, 0), (0, pm), (0, pk)))
    _, Mp, Kp = x.shape
    nk = Kp // bk
    out = pl.pallas_call(
        functools.partial(_kernel_batched, passes=passes, nk=nk),
        grid=(G, Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((bk, bn), lambda g, i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(x, w)
    if pm or pn:
        out = out[:, :M, :N]
    return out.reshape(*lead, M, N)
