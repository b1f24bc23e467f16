"""CPU rehearsal of ``chip_smoke.py``: the phase functions the chip run
calls, at the reduced qwen3-4b config, with the Pallas kernel in
interpret mode.  On the CPU the segmented tiers resolve to the XLA
reference, so no lowered decode holds a Pallas call here;
``tests/test_tpu_compile.py`` checks that they do for a described v5e."""
import importlib.util
import os

import pytest

from repro.serving import DEFAULT_TIERS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def sess(smoke):
    return smoke.build_session(reduced=True)


def test_chip_smoke_refuses_a_host_without_tpu(smoke, capsys):
    assert smoke.main() == 1
    out, err = capsys.readouterr()
    assert "platform=cpu" in out and '"ok"' not in out
    assert "needs a TPU" in err


def test_chip_smoke_kernel_phase(smoke, sess):
    smoke.check_kernels(sess.config, backend="interpret")


def test_chip_smoke_kernel_phase_catches_a_wrong_pass_count(smoke, sess,
                                                            monkeypatch):
    from repro.kernels import dispatch

    real = dispatch.matmul
    monkeypatch.setattr(dispatch, "matmul",
                        lambda x, w, passes, **kw: real(x, w, 2, **kw))
    smoke._kernel_vs_ref.clear_cache()
    try:
        with pytest.raises(smoke.SmokeFailure, match="bound|separate"):
            smoke.check_kernels(sess.config, backend="interpret")
    finally:
        smoke._kernel_vs_ref.clear_cache()


def test_chip_smoke_tier_phase(smoke, sess):
    assert smoke.check_tiers(sess, DEFAULT_TIERS) == {
        t.name: False for t in DEFAULT_TIERS}


def test_chip_smoke_serve_phase(smoke, sess):
    n = smoke.serve_and_check(sess, DEFAULT_TIERS)
    assert n == len(DEFAULT_TIERS) * smoke.REQUESTS_PER_TIER * smoke.NEW_TOKENS
