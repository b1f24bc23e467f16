"""The kernel substrate: backend dispatch, tuning tables, batched operands,
and golden bit-level vectors.

Three layers of guarantees:

1. dispatch plumbing — backend resolution (auto/legacy-force mapping),
   tuning-table lookups, and the NumericsConfig/registry entry points all
   route to the right implementation;
2. backend equivalence — the Pallas kernel bodies (interpret mode) are
   BIT-IDENTICAL to the XLA references across batched and odd shapes for
   the matmul (single contraction block, so the fp32 accumulation order
   matches the oracle's single dot) and the elementwise kernel, and
   ulp-tight for the SSD scan;
3. golden vectors — the bit-level AFPM datapath is pinned against a
   pure-Python integer reference (tests/golden/, regenerate with
   gen_afpm_golden.py).
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.afpm import AFPMConfig, afpm_mult_f32
from repro.core.numerics import LayerWeight, NumericsConfig, nmatmul
from repro.core.registry import get_elementwise, get_multiplier
from repro.kernels import dispatch, ops, ref

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "afpm_golden.json")


# ---------------------------------------------------------------------------
# dispatch plumbing
# ---------------------------------------------------------------------------

def test_resolve_backend_auto_and_explicit():
    native = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert dispatch.resolve_backend("auto") == native
    assert dispatch.resolve_backend("xla") == "xla"
    assert dispatch.resolve_backend("interpret") == "interpret"
    if jax.default_backend() == "tpu":
        assert dispatch.resolve_backend("pallas") == "pallas"
    else:
        # fail fast at the dispatch boundary, not deep in Mosaic lowering
        with pytest.raises(ValueError, match="requires a TPU"):
            dispatch.resolve_backend("pallas")


def test_resolve_backend_legacy_force_mapping():
    # the pre-substrate ops API: force= and interpret= keep working
    assert dispatch.resolve_backend("auto", force="xla") == "xla"
    assert dispatch.resolve_backend("auto", force="pallas", interpret=True) == "interpret"
    if jax.default_backend() == "tpu":
        assert dispatch.resolve_backend("auto", force="pallas") == "pallas"
    else:
        with pytest.raises(ValueError, match="requires a TPU"):
            dispatch.resolve_backend("auto", force="pallas")
    # an explicit backend wins over the legacy knob
    assert dispatch.resolve_backend("xla", force="pallas") == "xla"
    # bare interpret=True downgrades wherever pallas was selected — including
    # via auto (legacy: on CPU auto resolves to xla and interpret is ignored)
    native = "pallas" if jax.default_backend() == "tpu" else "xla"
    want = "interpret" if native == "pallas" else "xla"
    assert dispatch.resolve_backend("auto", interpret=True) == want
    assert dispatch.resolve_backend("pallas", interpret=True) == "interpret"


def test_resolve_backend_rejects_unknown():
    with pytest.raises(ValueError):
        dispatch.resolve_backend("tpu")
    with pytest.raises(ValueError):
        dispatch.resolve_backend("auto", force="interpret")


def test_tuning_tables_cover_all_buckets():
    for backend in ("pallas", "interpret"):
        for bucket in ("small", "medium", "large"):
            assert (backend, bucket) in dispatch.MATMUL_BLOCKS
            assert (backend, bucket) in dispatch.BITWISE_BLOCKS
            assert (backend, bucket) in dispatch.SCAN_CHUNKS
    assert dispatch.shape_bucket(128, 64) == "small"
    assert dispatch.shape_bucket(512, 64) == "medium"
    assert dispatch.shape_bucket(4096) == "large"
    # interpreter tiles are smaller than TPU tiles in every bucket
    for bucket in ("small", "medium", "large"):
        assert (dispatch.MATMUL_BLOCKS[("interpret", bucket)]
                < dispatch.MATMUL_BLOCKS[("pallas", bucket)])


def test_bitwise_block_bucket_boundaries_exact():
    """Regression: int(nelems ** 0.5) + 1 pushed exact-square boundary
    sizes one bucket up (65536 elems -> side 257 -> "medium"); the
    ceiling-isqrt bucketing keeps 256**2 in "small" and only crosses on
    65537."""
    assert dispatch.bitwise_block("interpret", 256 * 256) \
        == dispatch.BITWISE_BLOCKS[("interpret", "small")]
    assert dispatch.bitwise_block("interpret", 256 * 256 + 1) \
        == dispatch.BITWISE_BLOCKS[("interpret", "medium")]
    # the medium/large boundary follows the same rule (1024**2 elems)
    assert dispatch.bitwise_block("interpret", 1024 * 1024) \
        == dispatch.BITWISE_BLOCKS[("interpret", "medium")]
    assert dispatch.bitwise_block("interpret", 1024 * 1024 + 1) \
        == dispatch.BITWISE_BLOCKS[("interpret", "large")]
    # degenerate sizes bucket small instead of crashing isqrt
    assert dispatch.bitwise_block("interpret", 0) \
        == dispatch.BITWISE_BLOCKS[("interpret", "small")]
    assert dispatch.bitwise_block("interpret", 1) \
        == dispatch.BITWISE_BLOCKS[("interpret", "small")]


def test_ssd_xla_default_chunk_comes_from_table(rng):
    """The xla reference's chunk=None is tuned like every other backend
    (the legacy path hardcoded 128 regardless of L) and stays exact."""
    assert dispatch.scan_chunk("xla", 96) \
        == dispatch.SCAN_CHUNKS[("xla", "small")]
    assert dispatch.scan_chunk("xla", 2048) \
        == dispatch.SCAN_CHUNKS[("xla", "large")]
    L, H, P, N = 72, 2, 8, 4
    x = jnp.asarray(rng.standard_normal((L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (L, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((L, N)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((L, N)), jnp.float32)
    got = dispatch.ssd(x, dt, A, B, C, backend="xla")
    want = ref.ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_numerics_config_backend_validation():
    with pytest.raises(ValueError):
        NumericsConfig(mode="segmented", backend="cuda")
    assert NumericsConfig(backend="interpret").backend == "interpret"


def test_nmatmul_segmented_routes_through_dispatch(rng):
    x = jnp.asarray(rng.standard_normal((4, 24, 40)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    via_xla = nmatmul(x, w, NumericsConfig(mode="segmented", backend="xla"))
    via_interp = nmatmul(x, w, NumericsConfig(mode="segmented", backend="interpret"))
    want = ref.afpm_matmul_ref(x, w, 3)
    np.testing.assert_array_equal(np.asarray(via_xla), np.asarray(want))
    np.testing.assert_allclose(np.asarray(via_interp), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_registry_elementwise_backends_agree(rng):
    x = jnp.asarray(rng.standard_normal(777) * 4, jnp.float32)
    y = jnp.asarray(rng.standard_normal(777) * 4, jnp.float32)
    plain = get_multiplier("AC5-5")(x, y)
    for backend in ("xla", "interpret"):
        via = get_elementwise("AC5-5", backend=backend)(x, y)
        np.testing.assert_array_equal(np.asarray(via), np.asarray(plain))
    # non-AFPM designs fall back to the registered function
    assert get_elementwise("CSS16") is get_multiplier("CSS16")


# ---------------------------------------------------------------------------
# backend equivalence: pallas-interpret vs xla-ref, bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,bm", [
    # (lead..., M, K, N) and the row block: batched and odd/prime extents
    pytest.param((37, 43, 29), 37, id="shape0"),
    pytest.param((5, 37, 43, 29), 37, id="shape1"),
    pytest.param((2, 3, 17, 33, 9), 17, id="shape2"),
    # decode shapes: the one-token rows fold into one whole-extent block
    pytest.param((4, 1, 64, 48), 128, id="decode4"),
    pytest.param((8, 1, 64, 48), 128, id="decode8"),
    # 300 folded rows over bm=128: padded to 384, three row blocks
    pytest.param((3, 100, 64, 48), 128, id="padded_rows"),
])
@pytest.mark.parametrize("passes", [1, 3])
def test_matmul_interpret_bitwise_equals_xla(shape, bm, passes, rng):
    *lead_mk, N = shape
    x = jnp.asarray(rng.standard_normal(tuple(lead_mk)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((lead_mk[-1], N)), jnp.float32)
    # one contraction block => identical fp32 accumulation order to the oracle
    blocks = (bm, N, lead_mk[-1])
    got = dispatch.matmul(x, w, passes, backend="interpret", block_sizes=blocks)
    want = dispatch.matmul(x, w, passes, backend="xla")
    assert got.shape == tuple(lead_mk[:-1]) + (N,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _pallas_grids(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [e.params["grid_mapping"].grid for e in jaxpr.eqns
            if e.primitive.name == "pallas_call"]


def test_matmul_decode_rows_fold_into_one_grid():
    """A (8, 1, K) decode runs on the 2-D grid (rows/bm, N/bn, K/bk): the
    eight rows share one row block, so no batch axis walks the weight
    once per row."""
    x = jnp.zeros((8, 1, 256), jnp.float32)
    w = jnp.zeros((256, 96), jnp.float32)
    fn = functools.partial(dispatch.matmul, passes=1, backend="interpret",
                           block_sizes=(128, 32, 64))
    assert _pallas_grids(fn, x, w) == [(1, 3, 4)]
    assert fn(x, w).shape == (8, 1, 96)


@pytest.mark.parametrize("N", [48, 45])  # 45: the layer's tile pads
def test_matmul_layer_weight_reads_its_stack(N, rng):
    """A LayerWeight reaches the kernel as the whole stack and its layer
    index (N a multiple of bn), and the product equals the kernel's on
    that layer's slice bit for bit; a layer that needs padding is sliced
    and padded alone, never the stack.  The oracle slices the layer."""
    stack = jnp.asarray(rng.standard_normal((5, 64, N)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((8, 1, 64)), jnp.float32)
    fn = functools.partial(dispatch.matmul, passes=3, backend="interpret",
                           block_sizes=(128, 16, 64))
    layer = lambda s, i: fn(x, LayerWeight(s, i))  # noqa: E731
    got = jax.jit(layer)(stack, jnp.int32(3))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(fn(x, stack[3])))
    jaxpr = jax.make_jaxpr(layer)(stack, jnp.int32(3))
    call = next(e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
    assert call.invars[-1].aval.shape == ((5, 64, 48) if N == 48
                                          else (1, 64, 48))
    np.testing.assert_array_equal(
        np.asarray(dispatch.matmul(x, LayerWeight(stack, 3), 3, backend="xla")),
        np.asarray(dispatch.matmul(x, stack[3], 3, backend="xla")))


def test_multiply_broadcasts_on_every_backend(rng):
    """Broadcastable operands must behave identically across backends (the
    Pallas kernel itself requires equal shapes; dispatch broadcasts)."""
    cfg = AFPMConfig(n=5)
    x = jnp.asarray(rng.standard_normal((8, 5)) * 4, jnp.float32)
    y = jnp.float32(1.5)
    outs = [dispatch.multiply(x, y, cfg, backend=b) for b in ("xla", "interpret")]
    for out in outs:
        assert out.shape == (8, 5)
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


def test_ssd_default_chunk_comes_from_tuning_table(rng):
    """ops.ssd_scan with chunk=None consults the substrate's table (the old
    hardcoded 128 would skip it) and still matches the oracle."""
    L, H, P, N = 96, 2, 8, 4
    x = jnp.asarray(rng.standard_normal((L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (L, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((L, N)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((L, N)), jnp.float32)
    assert dispatch.scan_chunk("interpret", L) == 32  # not the legacy 128
    got = ops.ssd_scan(x, dt, A, B, C, backend="interpret")
    want = ref.ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", [(61,), (33, 77), (3, 65, 19)])
def test_bitwise_interpret_bitwise_equals_xla(shape, rng):
    cfg = AFPMConfig(n=5)
    x = jnp.asarray(rng.standard_normal(shape) * 4, jnp.float32)
    y = jnp.asarray(rng.standard_normal(shape) * 4, jnp.float32)
    got = dispatch.multiply(x, y, cfg, backend="interpret")
    want = dispatch.multiply(x, y, cfg, backend="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dims", [(64, 2, 16, 8, 16), (96, 3, 8, 4, 32),
                                  (128, 4, 32, 16, 32), (256, 4, 16, 8, 64)])
def test_ssd_interpret_bitwise_equals_xla(dims, rng):
    """Bit-exact, not merely ulp-close: both paths consume the hoisted
    ref.chunk_decay, so no fusion-context FP contraction can diverge them.
    chunk=16 is the regression shape — computed in-kernel, A*cumsum(dt) was
    contracted differently there and drifted by hundreds of ulp."""
    L, H, P, N, chunk = dims
    x = jnp.asarray(rng.standard_normal((L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (L, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((L, N)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((L, N)), jnp.float32)
    got = dispatch.ssd(x, dt, A, B, C, chunk=chunk, backend="interpret")
    want = dispatch.ssd(x, dt, A, B, C, chunk=chunk, backend="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ssd_interpret_equals_xla_under_jit(rng):
    """The bit-exactness guarantee must survive jit (the production entry
    point ops.ssd_scan is jitted): the hoisted decay sits behind a
    materialization boundary in both compiled programs."""
    L, H, P, N, chunk = 64, 2, 16, 8, 16
    x = jnp.asarray(rng.standard_normal((L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (L, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((L, N)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((L, N)), jnp.float32)
    got = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, backend="interpret")
    want = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, backend="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_batched_matmul_folds_rows(rng):
    """The jit'd wrapper folds leading batch dims into the kernel's row
    axis, restores them on the output, and matches the oracle on every
    element."""
    x = jnp.asarray(rng.standard_normal((3, 2, 48, 45)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((45, 29)), jnp.float32)
    got = ops.afpm_matmul(x, w, 3, backend="interpret")
    want = ref.afpm_matmul_ref(x, w, 3)
    assert got.shape == (3, 2, 48, 29)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_matmul_shape_validation_is_backend_uniform():
    for backend in ("xla", "interpret"):
        with pytest.raises(ValueError):
            dispatch.matmul(jnp.zeros((4, 8)), jnp.zeros((9, 4)), backend=backend)
        with pytest.raises(ValueError):
            dispatch.matmul(jnp.zeros((4, 8)), jnp.zeros((8,)), backend=backend)


def test_matmul_vector_lhs_on_every_backend(rng):
    """1-D x is promoted to (1, K) uniformly — the legacy ops wrapper
    accepted vectors, and auto must not crash only on one backend."""
    v = jnp.asarray(rng.standard_normal(24), jnp.float32)
    w = jnp.asarray(rng.standard_normal((24, 12)), jnp.float32)
    outs = [dispatch.matmul(v, w, 3, backend=b) for b in ("xla", "interpret")]
    for out in outs:
        assert out.shape == (12,)
    # GEMV lowers to a different XLA reduction strategy than the kernel's
    # (1, K) dot -> ulp-level wobble, not bit-exact like the 2-D cases
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_ssd_entry_point_pads_arbitrary_lengths(backend, rng):
    """dispatch.ssd itself must accept L not divisible by the (possibly
    auto-selected) chunk — padding is exact dt=0 steps."""
    L, H, P, N = 100, 2, 8, 4
    x = jnp.asarray(rng.standard_normal((L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (L, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((L, N)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((L, N)), jnp.float32)
    got = dispatch.ssd(x, dt, A, B, C, backend=backend)  # chunk auto-selected
    assert got.shape == (L, H, P)
    want = ref.ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# golden vectors: JAX datapath vs pure-Python bit-level reference
# ---------------------------------------------------------------------------

def _is_nan_bits(bits):
    return (((bits >> 23) & 0xFF) == 255) & ((bits & 0x7FFFFF) != 0)


def _golden_cases():
    with open(GOLDEN) as f:
        return json.load(f)["cases"]


@pytest.mark.parametrize("case", _golden_cases(), ids=lambda c: c["label"])
def test_afpm_golden_vectors(case):
    cfg = AFPMConfig(n=case["n"], mode=case["mode"], fmt=case["fmt"])
    x = jax.lax.bitcast_convert_type(
        jnp.asarray(case["x_bits"], jnp.uint32), jnp.float32)
    y = jax.lax.bitcast_convert_type(
        jnp.asarray(case["y_bits"], jnp.uint32), jnp.float32)
    got = np.asarray(
        jax.lax.bitcast_convert_type(afpm_mult_f32(x, y, cfg), jnp.uint32))
    want = np.asarray(case["out_bits"], np.uint32)
    # NaN payloads are unspecified; everything else is bit-exact
    ok = (got == want) | (_is_nan_bits(got) & _is_nan_bits(want))
    bad = np.where(~ok)[0]
    assert bad.size == 0, [
        (int(i), hex(case["x_bits"][i]), hex(case["y_bits"][i]),
         hex(int(got[i])), hex(int(want[i]))) for i in bad[:10]
    ]


def test_golden_file_covers_required_configs():
    labels = {c["label"] for c in _golden_cases()}
    assert {"AC5-5/fp32", "ACL4/fp32", "AC3-3/bf16", "ACL4/bf16"} <= labels
    for case in _golden_cases():
        assert len(case["x_bits"]) == len(case["y_bits"]) == len(case["out_bits"])
        assert len(case["x_bits"]) >= 256
