"""Kernel substrate: Pallas TPU kernels + portable backend dispatch.

  afpm_matmul  — segmented (split-float) approximate matmul on the MXU;
                 the TPU-native image of the paper's mantissa segmentation
  afpm_bitwise — bit-level AFPM datapath on the VPU (paper-faithful)
  ssd_scan     — Mamba2 SSD chunked scan (mamba2/zamba2 architectures)

Layering:

  dispatch.py  — backend resolution (auto | pallas | interpret | xla) and
                 per-kernel block-size lookups (measured autotuner table
                 first, static (backend, shape bucket) fallback); the
                 audited entry points
  autotune.py  — measure-and-cache block-size autotuner: versioned
                 ``TUNE_<device>.json`` artifacts, explicit activation,
                 swept out-of-band via ``python -m benchmarks.autotune``
  ref.py       — pure-jnp oracles defining each kernel's exact semantics
  ops.py       — jit'd public wrappers the model zoo calls

Tests validate the kernel bodies in ``interpret`` mode on CPU and pin
them against ``ref.py``; ``NumericsConfig.backend`` selects the backend
end-to-end.
"""
from . import autotune, dispatch, ops, ref

__all__ = ["autotune", "dispatch", "ops", "ref"]
