"""Jit'd public wrappers for the Pallas kernels.

Thin jit shells over the substrate's audited entry points in
``dispatch.py``: backend selection (``auto | pallas | interpret | xla``)
and block-size tuning live there; this module only pins the jit/static
argument surface the model zoo and benchmarks call.

The legacy ``force=``/``interpret=`` knobs from the pre-substrate API are
still accepted (``force="xla"`` == ``backend="xla"``, ``force="pallas",
interpret=True`` == ``backend="interpret"``) so existing call sites and
tests keep working; new code should pass ``backend=`` — typically straight
from ``NumericsConfig.backend``.
"""
from __future__ import annotations

import functools

import jax

from repro.core.afpm import AFPMConfig

from . import dispatch


@functools.partial(jax.jit,
                   static_argnames=("passes", "backend", "force", "interpret"))
def afpm_matmul(x, w, passes: int = 3, *, backend: str = "auto",
                force: str | None = None, interpret: bool = False):
    """Segmented approximate matmul; batch dims on ``x`` fold into the
    kernel's row axis, which shares each weight tile across them."""
    be = dispatch.resolve_backend(backend, force=force, interpret=interpret)
    return dispatch.matmul(x, w, passes, backend=be)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "backend", "force", "interpret"))
def afpm_multiply(x, y, cfg: AFPMConfig = AFPMConfig(), *, backend: str = "auto",
                  force: str | None = None, interpret: bool = False):
    """Elementwise bit-level AFPM multiply."""
    be = dispatch.resolve_backend(backend, force=force, interpret=interpret)
    return dispatch.multiply(x, y, cfg, backend=be)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "backend", "force", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int | None = None, backend: str = "auto",
             force: str | None = None, interpret: bool = False):
    """Chunked Mamba2 SSD scan: (L,H,P),(L,H),(H,),(L,N),(L,N) -> (L,H,P).

    ``chunk=None`` takes the substrate's tuned chunk for the resolved
    backend; arbitrary sequence lengths are handled (dispatch pads with
    exact dt=0 steps).  The xla backend uses the chunked jnp
    implementation (same FLOP structure as the kernel) so dry-run cost
    analysis reflects the real algorithm.
    """
    be = dispatch.resolve_backend(backend, force=force, interpret=interpret)
    return dispatch.ssd(x, dt, A, B, C, chunk=chunk, backend=be)
