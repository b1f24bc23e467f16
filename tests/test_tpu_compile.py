"""Compile the main-path kernels and one full-width qwen3-4b decode step
for a described TPU v5e chip, without the chip.

Nothing runs: these compiles catch what the chip's compiler refuses
(block shapes Mosaic cannot tile, programs that do not fit the device's
memory) at no chip time.  The topology is described inside a fixture, so
test collection never loads the TPU library; all of these tests live in
this one file, so one worker loads it.  The persistent compilation cache
is off around them: an entry compiled for a described chip cannot be
read back here.
"""
import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import dispatch
from repro.kernels.afpm_bitwise import afpm_bitwise_pallas
from repro.kernels.afpm_matmul import afpm_matmul_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.core.afpm import AFPMConfig
from repro.core.numerics import NumericsConfig
from repro.models import transformer
from repro.models.layers import unzip

V5E_HBM_BYTES = 15.75 * 2 ** 30   # what the compiler reports as usable
QWEN = get_arch("qwen3-4b")
D, Q, FF, VOCAB = (QWEN.d_model, QWEN.n_heads * QWEN.resolved_head_dim,
                   QWEN.d_ff, QWEN.vocab)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("passes", [1, 3])
@pytest.mark.parametrize("xs,ws", [
    ((4, 1, D), (D, Q)),          # decode: q projection, 4 rows
    ((4, 1, FF), (FF, D)),        # decode: MLP down projection
    ((1, 32, D), (D, Q)),         # full prefill chunk
    ((1, 7, D), (D, Q)),          # ragged last chunk
    ((4, 1, D), (D, VOCAB)),      # tied unembed (N not a multiple of 256)
    ((8, 1, D), (D, FF)),         # bulk decode: MLP up projection, 8 rows
], ids=["decode_q", "decode_down", "chunk32", "chunk7", "unembed",
        "bulk_decode_up"])
def test_afpm_matmul_compiles_for_v5e(one_chip, xs, ws, passes):
    bm, bn, bk = dispatch.matmul_block_sizes("pallas", xs[-2], xs[-1], ws[-1])
    fn = jax.jit(partial(afpm_matmul_pallas, passes=passes, bm=bm, bn=bn,
                         bk=bk))
    compiled = fn.lower(_sds(one_chip, xs, jnp.bfloat16),
                        _sds(one_chip, ws, jnp.bfloat16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_afpm_matmul_reference_keeps_its_roundings_for_v5e(one_chip):
    """The XLA reference's hi/lo split survives TPU compilation: an
    f32 -> bf16 -> f32 round trip there may be kept in f32 (excess
    precision), which zeroes the low segments."""
    from repro.kernels import ref

    fn = jax.jit(partial(ref.afpm_matmul_ref, passes=3))
    text = fn.lower(_sds(one_chip, (4, 1, D), jnp.float32),
                    _sds(one_chip, (D, Q), jnp.float32)).compile().as_text()
    assert text.count("reduce-precision(") >= 4  # hi and lo of x and w


def test_afpm_bitwise_compiles_for_v5e(one_chip):
    shape = (512, 512)
    block = dispatch.bitwise_block("pallas", shape[0] * shape[1])
    fn = jax.jit(partial(afpm_bitwise_pallas, cfg=AFPMConfig(), block=block))
    compiled = fn.lower(_sds(one_chip, shape, jnp.float32),
                        _sds(one_chip, shape, jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("L", [1024, 4096])
def test_ssd_scan_compiles_for_v5e_at_mamba2_130m_widths(one_chip, L):
    cfg = get_arch("mamba2-130m")
    s = cfg.ssm
    H, P, N = s.expansion * cfg.d_model // s.head_dim, s.head_dim, s.state_size
    fn = jax.jit(partial(ssd_scan_pallas,
                         chunk=dispatch.scan_chunk("pallas", L)))
    f32 = partial(_sds, one_chip, dtype=jnp.float32)
    compiled = fn.lower(f32((L, H, P)), f32((L, H)), f32((H,)), f32((L, N)),
                        f32((L, N))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compiled_decode_step(one_chip, monkeypatch, mode, rows=4):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        QWEN, numerics=NumericsConfig(mode=mode, seg_passes=3))
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params, _ = unzip(jax.eval_shape(partial(transformer.init, cfg),
                                     jax.random.PRNGKey(0)))
    state = jax.eval_shape(partial(transformer.init_state, cfg, rows, 256,
                                   dtype=jnp.bfloat16))

    def step(p, tok, st, pos):
        return transformer.decode_step(p, cfg, {"token": tok}, st, pos)

    compiled = jax.jit(step).lower(
        place(params), _sds(one_chip, (rows, 1), jnp.int32), place(state),
        _sds(one_chip, (), jnp.int32)).compile()
    return params, compiled


@pytest.mark.parametrize("mode", ["exact", "segmented"])
def test_qwen3_4b_decode_step_fits_one_v5e(one_chip, monkeypatch, mode):
    """One decode step at published widths (batch 4, 256 cache positions)
    with bf16 weights fits the chip, and the segmented step runs the
    Pallas kernel (``auto`` resolves to it on a TPU)."""
    params, compiled = _compiled_decode_step(one_chip, monkeypatch, mode)
    # every weight is bf16; only the (stacked) norm scales stay float32
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert {jax.tree_util.keystr(k[-1:]) for k, s in leaves
            if s.dtype != jnp.bfloat16} == {"['scale']"}
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used / 2 ** 30:.2f} GiB"
    has_kernel = "tpu_custom_call" in compiled.as_text()
    assert has_kernel == (mode == "segmented")


def test_qwen3_4b_segmented_decode_reads_weights_from_their_stack(
        one_chip, monkeypatch):
    """Each of a layer's seven kernel calls takes the whole stack of its
    weight, ``bf16[36, K, N]``, and the layer index: the layer scan copies
    no weight out of its stack before the kernel (a bulk decode, 8 rows,
    one row block)."""
    _, compiled = _compiled_decode_step(one_chip, monkeypatch, "segmented",
                                        rows=8)
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    L = QWEN.segments[0][0]
    assert len(calls) == 7
    assert all(f"bf16[{L}," in ln and "f32[8," in ln for ln in calls), calls
