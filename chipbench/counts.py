"""Operations and bytes, computed from a configuration's shapes.

The kernel counts follow ``kernels/afpm_matmul.py``'s contract: one call
multiplies ``x (M, K)`` by ``w (K, N)`` in ``passes`` bf16 MXU passes, so
it needs ``2 M N K * passes`` operations and, at the least, reads ``x``
and ``w`` once at their stored dtypes and writes the float32 output once.
Model operations count what a token needs whatever the tier: two per
matmul weight, plus attention's score and value products over the live
context.
"""
from __future__ import annotations

import json
from pathlib import Path

from chipbench.model import Spec

ROOT = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    with open(ROOT / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; known: "
                       f"{sorted(k for k in table if k != 'source')}")
    return table[device_kind]


def layer_matmuls(spec: Spec) -> list:
    """``(name, K, N)`` of every weight matmul in one decoder layer, in the
    order the layer runs them; each goes through ``nmatmul`` (the Pallas
    kernel in a segmented tier)."""
    d, q, kv = spec.d, spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("wi", d, spec.ff), ("wg", d, spec.ff), ("w2", spec.ff, spec.d)]


def kernel_cost(M: int, K: int, N: int, passes: int, x_bytes: int = 2,
                w_bytes: int = 2, out_bytes: int = 4) -> tuple:
    """(operations, bytes) of one segmented-matmul kernel call."""
    return (2 * M * K * N * passes,
            M * K * x_bytes + K * N * w_bytes + M * N * out_bytes)


def forward_kernel_calls(spec: Spec, rows: int, tokens: int,
                         passes: int) -> list:
    """(operations, bytes) of every kernel call in one forward of ``rows``
    rows of ``tokens`` tokens each: the batched kernel runs row by row, one
    call per matmul per layer with ``M = tokens`` for each row's slice.

    Returned as one entry per matmul per layer; the row count multiplies
    operations and activation bytes but not the weight read, which the
    roofline counts once."""
    out = []
    for _ in range(spec.layers):
        for _, K, N in layer_matmuls(spec):
            ops, _ = kernel_cost(rows * tokens, K, N, passes)
            byt = rows * tokens * (K * 2 + N * 4) + K * N * 2
            out.append((ops, byt))
    return out


def kernels_per_forward(spec: Spec) -> int:
    """Kernel calls one forward makes in a segmented tier (the head is an
    XLA dot in every tier)."""
    return spec.layers * len(layer_matmuls(spec))


def matmul_params(spec: Spec) -> int:
    """Weights a token multiplies: every layer's matmuls and the head."""
    per_layer = sum(K * N for _, K, N in layer_matmuls(spec))
    return spec.layers * per_layer + spec.d * spec.vocab


def token_flops(spec: Spec, context: int) -> int:
    """Model operations for one token that attends ``context`` positions
    (itself included)."""
    attn = 4 * spec.layers * spec.heads * spec.head_dim * context
    return 2 * matmul_params(spec) + attn


def decode_weight_bytes(spec: Spec) -> int:
    """Bytes of weight a decode step reads at least once: every matmul
    weight and the head at the stored dtype, plus the embedding rows and
    norms (negligible, not counted)."""
    return matmul_params(spec) * spec.dtype.itemsize


def kv_bytes_per_token(spec: Spec) -> int:
    """Bytes of keys and values one position holds in the paged cache."""
    return (spec.layers * 2 * spec.kv_heads * spec.head_dim
            * spec.dtype.itemsize)
