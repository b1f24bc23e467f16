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

The weight is 2-D and shared by every row of ``x``, so the leading dims
of ``x`` fold into its row axis: ``(..., M, K)`` runs as ``(R, K)`` with
``R = prod(lead) * M`` on one (R/bm, N/bn, K/bk) grid with k innermost.
Each weight tile is then read, split and loaded into the MXU once per
call, not once per batch row — a decode's B one-token rows make one
B-row block.  The fp32->bf16 split happens per tile in VMEM, so HBM
traffic is the operands read once — arithmetic intensity is identical
to a plain matmul while the MXU work is 1-3 bf16 passes instead of 6
(fp32 emulation) per tile.

The weight may also be one layer of a stack ``(L, K, N)``: the layer
index is a scalar-prefetch operand that the weight's index map reads, so
the kernel fetches that layer's tiles from the stack in HBM itself, and
a layer scan makes no per-layer copy of the weight before the call.

Block sizes default to the substrate's tuning tables via
``kernels/dispatch.py``.
"""
from __future__ import annotations

import functools
import math

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


def _kernel(layer_ref, x_ref, w_ref, o_ref, acc_ref, *, passes: int,
            nk: int):
    del layer_ref  # read by the weight's index map only

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref[...], w_ref[...], acc_ref, passes=passes)

    @pl.when(pl.program_id(2) == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _pad2(t, p0, p1):
    return jnp.pad(t, ((0, p0), (0, p1))) if p0 or p1 else t


def afpm_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    passes: int = 3,
    *,
    layer=None,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Segmented matmul ``x (..., M, K) @ w (K, N) -> (..., M, N) fp32``.

    The leading dims of ``x`` fold into the row axis, so every row shares
    each weight tile.  The row block is ``min(bm, R)``: the whole row
    extent when it fits in one block (no padding), else ``bm`` with the
    rows padded to a multiple of it.  With ``layer`` (an int32 scalar),
    ``w`` is a stack ``(L, K, N)`` and the product uses ``w[layer]``, read
    from the stack in place.
    """
    if x.ndim < 2 or w.ndim != (2 if layer is None else 3):
        raise ValueError(f"need x (..., M, K) @ w ([L,] K, N) with L only "
                         f"for a layer; got {x.shape} @ {w.shape}")
    *lead, M, K = x.shape
    K2, N = w.shape[-2:]
    if K != K2:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    if layer is None:
        w, layer = w[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    R = math.prod(x.shape[:-1])
    bm = min(bm, R)
    bn = min(bn, N)
    bk = min(bk, K)
    pm, pn, pk = (-R) % bm, (-N) % bn, (-K) % bk
    x = _pad2(x.reshape(R, K), pm, pk)
    if pn or pk:
        # padding the stack would copy every layer: pad this layer alone
        w = _pad2(jax.lax.dynamic_index_in_dim(w, layer[0], keepdims=False),
                  pk, pn)[None]
        layer = jnp.zeros((1,), jnp.int32)
    Rp, Kp = x.shape
    Np = w.shape[2]
    nk = Kp // bk
    out = pl.pallas_call(
        functools.partial(_kernel, passes=passes, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Rp // bm, Np // bn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, l: (i, k)),
                pl.BlockSpec((pl.Squeezed(), bk, bn),
                             lambda i, j, k, l: (l[0], k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, l: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Rp, Np), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(layer, x, w)
    if pm or pn:
        out = out[:R, :N]
    return out.reshape(*lead, M, N)
