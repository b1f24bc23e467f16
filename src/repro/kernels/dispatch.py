"""Backend dispatch for the kernel substrate.

One audited entry point per kernel (``matmul`` / ``multiply`` / ``ssd``),
each taking a ``backend`` knob:

  ``auto``       pallas on TPU, xla everywhere else (the production default)
  ``pallas``     native Pallas lowering (requires a TPU backend)
  ``interpret``  the Pallas kernel body executed in interpreter mode —
                 runs on CPU/GPU, used by tests to validate the kernels
  ``xla``        the pure-jnp reference implementation (``ref.py``)

Block/tile sizes are no longer hardcoded in the kernels: every lookup
consults the measured-and-cached autotuner table first
(:mod:`repro.kernels.autotune`, keyed on ``(kernel, backend, shape
bucket, device_kind)`` and activated explicitly — never tuned implicitly
on a hot path) and falls back to the static per-bucket tables below, so
the interpreter path uses small tiles (fast to simulate) while the TPU
path uses MXU/VMEM-sized tiles.  Callers can still override explicitly.
With no tuned artifact activated, behavior is bit-identical to the
static tables.

``repro.core.numerics.NumericsConfig.backend`` feeds straight into this
module; the jit'd public wrappers live in ``ops.py``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.afpm import AFPMConfig
from repro.core.numerics import BACKENDS, LayerWeight, NumericsConfig

from . import autotune, ref
from .afpm_bitwise import afpm_bitwise_pallas
from .afpm_matmul import afpm_matmul_pallas
from .ssd_scan import ssd_scan_pallas


def resolve_backend(backend: str = "auto", *, force: str | None = None,
                    interpret: bool = False) -> str:
    """Resolve a backend request to one of ``pallas | interpret | xla``.

    ``force``/``interpret`` are the legacy knobs of the pre-substrate
    ``ops`` API (``force="pallas"|"xla"``, ``interpret=True``); they are
    honored only when ``backend`` is left at ``auto``.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend == "auto" and force is not None:
        if force not in ("pallas", "xla"):
            raise ValueError(f"unknown force={force!r}; expected 'pallas' or 'xla'")
        backend = force
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    # the interpret downgrade applies wherever pallas was selected —
    # including via auto — matching the legacy interpret=True semantics
    if backend == "pallas" and interpret:
        backend = "interpret"
    if backend == "pallas" and jax.default_backend() != "tpu":
        raise ValueError(
            "backend='pallas' requires a TPU host; use 'interpret' to run "
            "the kernel body on CPU/GPU, or 'xla' for the reference")
    return backend


# -- block-size tuning tables -----------------------------------------------

def shape_bucket(*dims: int) -> str:
    """Bucket a shape by its largest extent: small / medium / large."""
    m = max(dims) if dims else 0
    if m <= 256:
        return "small"
    if m <= 1024:
        return "medium"
    return "large"


# (bm, bn, bk) for the segmented matmul.  TPU tiles are MXU-sized and grow
# the contraction block with the problem; interpreter tiles stay small so a
# CPU test sweep simulates few grid steps over little data.
MATMUL_BLOCKS = {
    ("pallas", "small"): (128, 128, 128),
    ("pallas", "medium"): (256, 256, 256),
    ("pallas", "large"): (256, 256, 512),
    ("interpret", "small"): (32, 32, 32),
    ("interpret", "medium"): (64, 64, 64),
    ("interpret", "large"): (128, 128, 128),
}

# (rows, cols) flat tile for the elementwise bit-level kernel.
BITWISE_BLOCKS = {
    ("pallas", "small"): (256, 256),
    ("pallas", "medium"): (256, 256),
    ("pallas", "large"): (512, 256),
    ("interpret", "small"): (32, 64),
    ("interpret", "medium"): (64, 128),
    ("interpret", "large"): (128, 256),
}

# SSD scan chunk length (the sequential grid step).  The xla reference
# is chunked too — its chunk follows the same tuning policy instead of
# the formerly hardcoded 128.
SCAN_CHUNKS = {
    ("pallas", "small"): 128,
    ("pallas", "medium"): 128,
    ("pallas", "large"): 256,
    ("interpret", "small"): 32,
    ("interpret", "medium"): 64,
    ("interpret", "large"): 128,
    ("xla", "small"): 128,
    ("xla", "medium"): 128,
    ("xla", "large"): 256,
}


def matmul_block_sizes(backend: str, M: int, K: int, N: int):
    bucket = shape_bucket(M, K, N)
    tuned = autotune.lookup("matmul", backend, bucket)
    return tuned if tuned is not None else MATMUL_BLOCKS[(backend, bucket)]


def bitwise_block(backend: str, nelems: int):
    # bucket by the side of the square an nelems-flat operand tiles into,
    # ceiling-rounded: 65536 elems -> extent 256 -> "small" (the old
    # int(nelems ** 0.5) + 1 pushed exact-boundary sizes a bucket up)
    side = math.isqrt(max(nelems, 1))
    if side * side < nelems:
        side += 1
    bucket = shape_bucket(side)
    tuned = autotune.lookup("bitwise", backend, bucket)
    return tuned if tuned is not None else BITWISE_BLOCKS[(backend, bucket)]


def scan_chunk(backend: str, L: int) -> int:
    bucket = shape_bucket(L)
    tuned = autotune.lookup("ssd", backend, bucket)
    return tuned if tuned is not None else SCAN_CHUNKS[(backend, bucket)]


# -- audited kernel entry points --------------------------------------------

def reads_layers_in_place(ncfg) -> bool:
    """True when every matmul under ``ncfg`` runs the segmented Pallas
    kernel (one plain config, not a per-layer policy), which reads a
    :class:`LayerWeight` from its stack in place."""
    return (isinstance(ncfg, NumericsConfig) and ncfg.mode == "segmented"
            and resolve_backend(ncfg.backend) != "xla")


def matmul(x, w, passes: int = 3, *, backend: str = "auto",
           block_sizes=None) -> jax.Array:
    """Segmented approximate matmul ``x (..., K) @ w (K, N)``.

    The leading dims of batched (3-D+) ``x`` fold into the kernel's row
    axis, so each weight tile is read once per call, not once per batch
    row; the xla backend is the ``ref.afpm_matmul_ref`` oracle.  ``w``
    may be a :class:`LayerWeight`, which the kernel reads from its stack
    in place and the oracle slices.
    Validation and 1-D promotion happen here, before the backend branch,
    so every backend accepts the same inputs.
    """
    backend = resolve_backend(backend)
    if x.ndim < 1 or w.ndim != 2:
        raise ValueError(f"need x (..., K) @ w (K, N); got {x.shape} @ {w.shape}")
    if x.shape[-1] != w.shape[0]:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    vec = x.ndim == 1
    if vec:
        x = x[None, :]
    if backend == "xla":
        if isinstance(w, LayerWeight):
            w = w.value()
        out = ref.afpm_matmul_ref(x, w, passes)
    else:
        if block_sizes is None:
            block_sizes = matmul_block_sizes(
                backend, x.shape[-2], x.shape[-1], w.shape[-1])
        bm, bn, bk = block_sizes
        w, layer = ((w.stack, w.index) if isinstance(w, LayerWeight)
                    else (w, None))
        out = afpm_matmul_pallas(x, w, passes, layer=layer, bm=bm, bn=bn,
                                 bk=bk, interpret=backend == "interpret")
    return out[0] if vec else out


def multiply(x, y, cfg: AFPMConfig = AFPMConfig(), *, backend: str = "auto",
             block=None) -> jax.Array:
    """Elementwise bit-level AFPM multiply under ``cfg``.

    Operands are broadcast first so every backend accepts the same inputs
    (the Pallas kernel itself requires equal shapes)."""
    x, y = jnp.broadcast_arrays(x, y)
    backend = resolve_backend(backend)
    if backend == "xla":
        return ref.afpm_bitwise_ref(x, y, cfg)
    if block is None:
        block = bitwise_block(backend, x.size)
    return afpm_bitwise_pallas(x, y, cfg, block=block,
                               interpret=backend == "interpret")


def ssd(x, dt, A, B, C, *, chunk: int | None = None,
        backend: str = "auto") -> jax.Array:
    """Mamba2 SSD chunked scan ``(L,H,P),(L,H),(H,),(L,N),(L,N) -> (L,H,P)``.

    ``chunk=None`` takes the tuned chunk for the resolved backend — every
    backend, the xla reference included, goes through the same
    ``scan_chunk`` lookup (tuned table first, static fallback); any
    sequence length is accepted — non-multiples of the chunk are padded
    with dt=0 steps (exact: zero decay increment and zero input weight)
    and sliced back.
    """
    backend = resolve_backend(backend)
    L = x.shape[0]
    if chunk is None:
        chunk = scan_chunk(backend, L)
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, pad), (0, 0)))
        B = jnp.pad(B, ((0, pad), (0, 0)))
        C = jnp.pad(C, ((0, pad), (0, 0)))
    if backend == "xla":
        out = ref.ssd_scan_chunked_ref(x, dt, A, B, C, chunk=Q)
    else:
        out = ssd_scan_pallas(x, dt, A, B, C, chunk=Q,
                              interpret=backend == "interpret")
    return out[:L] if pad else out
