"""Numerics configuration + matmul dispatch — the "compiler integration" layer.

This is the system-level face of the paper: floating-point precision and
multiplier architecture are exposed as first-class configuration, and every
matmul in the model zoo routes through :func:`nmatmul`.

Modes
-----
``exact``
    Native IEEE fp32 (or bf16) matmul — the exact-baseline row.
``emulated``
    Every scalar product goes through the bit-level multiplier selected by
    ``multiplier`` (AC-n-n / ACL-n / MMBS / CSS / NC-LPC-HPC).  Bit-faithful
    to the RTL; used for the paper's accuracy studies (Tables III/IV).
    O(M*N*K) elementwise work — small models only.
``segmented``
    TPU-native analogue: split-float (hi/lo bf16) matmul with term
    skipping; ``seg_passes`` = 1 (ACL-like), 2, or 3 (AC-n-n-like) MXU
    passes, exact = 6-pass HIGHEST.  Scales to the full model zoo and is
    what the multi-pod dry-run/roofline paths use.  Backed by the kernel
    substrate (``repro.kernels.dispatch``), selected by ``backend``:

    ``auto``       Pallas on TPU, XLA reference elsewhere (default)
    ``pallas``     force the native Pallas lowering (TPU)
    ``interpret``  Pallas kernel body in interpreter mode (any backend;
                   what tests use to validate the kernels on CPU)
    ``xla``        force the pure-jnp reference implementation

Configuration is *ambient*: ``repro.core.scope`` provides the
``numerics_scope`` / ``layer_scope`` context managers (public surface:
``repro.numerics``), and :func:`nmatmul` with no extra arguments resolves
its config from the innermost scope and its full layer path from the
scope stack.  Per-layer policies (``repro.core.policy``: glob rules over
layer paths) plug in as the scoped value.  The legacy explicit form
``nmatmul(x, w, cfg, path=...)`` still works for one release behind a
:class:`DeprecationWarning`.
"""
from __future__ import annotations

import dataclasses
import sys
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from . import scope as _scope
from .afpm import AFPMConfig, afpm_matmul_emulated
from .registry import get_elementwise, get_multiplier

# single source of truth for kernel backends; kernels/dispatch.py imports
# this (that direction is cycle-safe, the reverse is not: EXACT below is
# constructed while this module loads)
BACKENDS = ("auto", "pallas", "interpret", "xla")


@dataclasses.dataclass(frozen=True)
class NumericsConfig:
    mode: str = "exact"             # exact | emulated | segmented
    multiplier: str = "AC5-5"       # registry name, for emulated mode
    seg_passes: int = 3             # segmented mode: 1=ACL-like, 3=AC-like
    seg_n: int = 5                  # segment width for emulated AC modes
    backend: str = "auto"           # kernel backend: auto|pallas|interpret|xla
    compute_dtype: str = "bfloat16" # exact-mode matmul dtype for big models
    accum_dtype: str = "float32"

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of {BACKENDS}")

    def afpm(self) -> AFPMConfig:
        mode = "acl" if self.multiplier.lower().startswith("acl") else "ac"
        return AFPMConfig(n=self.seg_n, mode=mode)


EXACT = NumericsConfig(mode="exact")


class LayerWeight:
    """Layer ``index`` of a stacked weight ``stack (L, K, N)``, left
    unsliced: the segmented Pallas kernel reads that layer's tiles from the
    stack in place, so a layer scan copies no weight before the call.
    Every other consumer takes :meth:`value`, the ``(K, N)`` slice."""

    __slots__ = ("stack", "index")
    ndim = 2

    def __init__(self, stack, index):
        self.stack, self.index = stack, index

    @property
    def shape(self):
        return self.stack.shape[1:]

    @property
    def dtype(self):
        return self.stack.dtype

    def value(self) -> jax.Array:
        return jax.lax.dynamic_index_in_dim(self.stack, self.index,
                                            keepdims=False)


# ---------------------------------------------------------------------------
# calibration tap — the instrumented-pass hook for repro.core.sensitivity
# ---------------------------------------------------------------------------
# When a tap is installed, nmatmul reports (full layer path, x, w) for every
# call site it executes with concrete (non-traced) operands; sites inside
# jax.lax.scan / jit traces see tracers and are skipped, which is why the
# sensitivity calibration pass forces policy-driven unrolling
# (NumericsPolicy.force_unroll) and runs eagerly.
_OPERAND_TAP = None


def set_operand_tap(tap):
    """Install (``tap(path, x, w)``) or clear (``tap=None``) the call-site
    operand recorder; returns the previously installed tap so callers can
    restore it (see ``repro.core.sensitivity.record_operands``)."""
    global _OPERAND_TAP
    prev = _OPERAND_TAP
    _OPERAND_TAP = tap
    return prev


def operand_tap_active() -> bool:
    """True while a calibration tap is installed — call sites that normally
    bypass nmatmul for exact numerics (native convs, the fused routed-expert
    einsum) must route through it so the pass records their operands."""
    return _OPERAND_TAP is not None


def segmented_matmul_xla(x, w, passes: int = 3):
    """Split-float approximate matmul (XLA reference; oracle for the kernel).

    passes=3: hi*hi + hi*lo + lo*hi  (AC + AD + BC; BD omitted, paper Eq. 6)
    passes=2: hi*hi + hi*lo          (asymmetric: activations low bits kept)
    passes=1: hi*hi                  (ACL-like single high-segment product)

    Thin alias of ``repro.kernels.ref.afpm_matmul_ref`` — the single XLA
    reference implementation, also what the substrate's xla backend runs.
    """
    from repro.kernels import ref  # lazy: kernels import core

    return ref.afpm_matmul_ref(x, w, passes)


# call sites (by code location) that already emitted the one-per-site
# nmatmul deprecation warning; repro.numerics.reset_deprecation_registry
# clears it (tests)
_DEPRECATED_SITES: set = set()


def _warn_deprecated_nmatmul():
    frame = sys._getframe(2)  # the nmatmul caller
    site = (frame.f_code.co_filename, frame.f_lineno)
    if site in _DEPRECATED_SITES:
        return
    _DEPRECATED_SITES.add(site)
    warnings.warn(
        "nmatmul(x, w, cfg, path=...) is deprecated; wrap the call in "
        "repro.numerics.numerics_scope(cfg) / layer_scope(name) and call "
        "nmatmul(x, w) — the explicit form will be removed next release",
        DeprecationWarning, stacklevel=3)


def nmatmul(x: jax.Array, w: jax.Array, cfg: Optional[NumericsConfig] = None,
            path: Optional[str] = None):
    """Numerics-aware matmul: ``x @ w`` under the ambient numerics scope.

    The config comes from the innermost ``repro.numerics.numerics_scope``
    (EXACT outside any scope); for policies it is resolved per call site
    against the full layer path of the active ``layer_scope`` stack — this
    is what lets one forward pass run different numerics in different
    layers without threading arguments.

    Deprecated form: ``cfg`` (config or policy/scoped-policy) and ``path``
    may still be passed explicitly; an explicit ``cfg`` shadows any
    ambient scope, while ``path`` alone resolves the ambient scope at that
    leaf (like an inline ``layer_scope``).  Both warn once per call site
    and will be removed one release after 2026-07.
    """
    if cfg is None and path is None:
        amb = _scope.current_numerics()
        rel = _scope.current_path()
        # a scoped-policy ambient (e.g. block_apply(ncfg=policy.scope(...)))
        # carries a prefix: the tap must see the absolute path even though
        # resolution below stays relative (ScopedPolicy.lookup joins it)
        full = amb.full_path(rel) if hasattr(amb, "full_path") else rel
        if amb is None:
            resolved = EXACT
        elif isinstance(amb, NumericsConfig):
            resolved = amb
        else:
            resolved = amb.lookup(rel)
    else:
        _warn_deprecated_nmatmul()
        path = path or ""
        if cfg is None:
            # path-only call (half-migrated site): treat the path as an
            # inline layer_scope leaf and resolve the ambient scope there —
            # silently dropping an active policy would skew results
            amb = _scope.current_numerics()
            rel = _scope.current_path(path)
            full = amb.full_path(rel) if hasattr(amb, "full_path") else rel
            if amb is None:
                resolved = EXACT
            elif isinstance(amb, NumericsConfig):
                resolved = amb
            else:
                resolved = amb.lookup(rel)
        else:
            # full path: a scoped policy knows its prefix; plain configs
            # report the caller-supplied (relative) path verbatim
            full = cfg.full_path(path) if hasattr(cfg, "full_path") else path
            if isinstance(cfg, NumericsConfig):
                resolved = cfg
            else:
                resolved = cfg.lookup(path)  # NumericsPolicy / ScopedPolicy
                # (duck-typed to keep core.numerics import-cycle-free)
    if isinstance(w, LayerWeight) and (resolved.mode != "segmented"
                                       or _OPERAND_TAP is not None):
        w = w.value()
    if _OPERAND_TAP is not None and not (
            isinstance(x, jax.core.Tracer) or isinstance(w, jax.core.Tracer)):
        _OPERAND_TAP(full, x, w)
    cfg = resolved
    if cfg.mode == "exact":
        dt = jnp.dtype(cfg.compute_dtype)
        return jax.lax.dot_general(
            x.astype(dt), w.astype(dt), (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.dtype(cfg.accum_dtype),
        )
    if cfg.mode == "emulated":
        name = cfg.multiplier.lower()
        if name.startswith(("ac", "acl")) and not name.startswith("ac-"):
            return afpm_matmul_emulated(x, w, cfg.afpm())
        # generic registry multiplier: chunked elementwise matmul
        mult = get_multiplier(cfg.multiplier)
        return _generic_emulated_matmul(x, w, mult)
    if cfg.mode == "segmented":
        from repro.kernels import dispatch  # lazy: kernels import core

        return dispatch.matmul(x, w, cfg.seg_passes, backend=cfg.backend)
    raise ValueError(f"unknown numerics mode {cfg.mode!r}")


def _generic_emulated_matmul(x, w, mult, k_chunk: int = 64):
    x = jnp.asarray(x, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    K = x.shape[-1]
    pad = (-K) % k_chunk
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        w = jnp.pad(w, [(0, pad), (0, 0)])
    nchunks = x.shape[-1] // k_chunk
    xs = jnp.moveaxis(x.reshape(x.shape[:-1] + (nchunks, k_chunk)), -2, 0)
    ws = w.reshape(nchunks, k_chunk, w.shape[-1])

    def body(carry, kc):
        xk, wk = kc
        return carry + jnp.sum(mult(xk[..., :, None], wk), axis=-2), None

    init = jnp.zeros(x.shape[:-1] + (w.shape[-1],), jnp.float32)
    out, _ = jax.lax.scan(body, init, (xs, ws))
    return out


def apply_elementwise(x, y, multiplier: str, backend: str = "auto"):
    """Elementwise product under a named multiplier (image-processing path).

    AFPM-family multipliers route through the kernel substrate (Pallas on
    TPU); everything else runs the registered pure-jnp function.
    """
    return get_elementwise(multiplier, backend=backend)(x, y)
