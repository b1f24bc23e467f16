"""Session facade: one (arch, policy, backend, mesh) spec behind serve /
dryrun / the sweep; policy loading with one-line errors; the serve CLI's
non-zero exit on malformed policy files."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.metrics import mred
from repro.core.numerics import NumericsConfig
from repro.core.policy import NumericsPolicy, PolicyRule
from repro.models import resnet, transformer
from repro.models.layers import unzip
from repro.session import GenerateResult, Session, SessionError, load_policy

SEG1 = NumericsConfig(mode="segmented", seg_passes=1, backend="xla")
SEG3 = NumericsConfig(mode="segmented", seg_passes=3, backend="xla")


# ---------------------------------------------------------------------------
# construction / policy coercion
# ---------------------------------------------------------------------------

def test_session_presets_and_config():
    from repro.kernels import dispatch

    s = Session("qwen3-4b", policy="segmented1")
    # presets leave the kernel to the platform: Pallas on a TPU, the XLA
    # reference (SEG1's arithmetic) everywhere else
    assert s.config.numerics == dataclasses.replace(SEG1, backend="auto")
    assert dispatch.resolve_backend(s.config.numerics.backend) == "xla"
    assert not s.is_policy
    # "exact" keeps the arch's own numerics
    assert Session("qwen3-4b", policy="exact").config.numerics == \
        Session("qwen3-4b").config.numerics
    # reduced by default; full-size on request
    assert Session("qwen3-4b").config.d_model < \
        Session("qwen3-4b", reduced=False).config.d_model


def test_session_accepts_ready_config_and_policy_object():
    from repro.configs import get_arch

    cfg = get_arch("qwen3-4b").reduced()
    pol = NumericsPolicy((PolicyRule("blocks.*.mlp.*", SEG1),))
    s = Session(cfg, policy=pol)
    assert s.is_policy and s.config.numerics == pol
    assert s.arch_id == cfg.arch_id


def test_session_backend_override_rewrites_all_configs():
    s = Session("qwen3-4b", policy="segmented1", backend="interpret")
    assert s.config.numerics.backend == "interpret"
    pol = NumericsPolicy((PolicyRule("a", SEG1),), default=SEG3)
    sp = Session("qwen3-4b", policy=pol, backend="interpret")
    num = sp.config.numerics
    assert num.default.backend == "interpret"
    assert all(r.config.backend == "interpret" for r in num.rules)


def test_session_policy_json_file_round_trip(tmp_path):
    pol = NumericsPolicy((PolicyRule("blocks.*", SEG1),), default=SEG3)
    p = tmp_path / "policy.json"
    p.write_text(pol.to_json())
    s = Session("qwen3-4b", policy=str(p))
    assert s.config.numerics == pol
    assert load_policy(str(p)) == pol


def test_session_policy_errors_are_one_line():
    with pytest.raises(SessionError, match="cannot read policy file"):
        Session("qwen3-4b", policy="/does/not/exist.json")
    with pytest.raises(SessionError, match="unknown arch"):
        Session("no-such-arch")
    with pytest.raises(SessionError, match="unsupported policy spec"):
        Session("qwen3-4b", policy=3.14)
    # a ScopedPolicy view is prefixed — rejected up front instead of
    # crashing later in ppa_report/save_policy/_with_backend
    pol = NumericsPolicy((PolicyRule("blocks.*", SEG1),))
    with pytest.raises(SessionError, match="ScopedPolicy"):
        Session("qwen3-4b", policy=pol.scope("blocks.0"))


def test_session_replace_rejects_unknown_fields():
    s = Session("qwen3-4b")
    with pytest.raises(SessionError, match="unknown Session.replace field"):
        s.replace(polcy=SEG1)  # typo must not silently no-op


def test_session_policy_malformed_json(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{this is not json")
    with pytest.raises(SessionError, match="invalid policy JSON"):
        Session("qwen3-4b", policy=str(bad))
    # valid JSON, invalid schema (unknown config field)
    bad.write_text(json.dumps(
        {"rules": [{"pattern": "x", "config": {"use_pallas": True}}]}))
    with pytest.raises(SessionError, match="invalid policy JSON"):
        Session("qwen3-4b", policy=str(bad))


# ---------------------------------------------------------------------------
# PPA report / layer enumeration
# ---------------------------------------------------------------------------

def test_session_ppa_report_matches_manual_rollup():
    from repro.core import sweep

    s = Session("qwen3-4b", policy="segmented1")
    rep = s.ppa_report()
    paths = transformer.layer_paths(s.config)
    assert rep["n_sites"] == len(paths)
    assert rep["area_um2"] == pytest.approx(
        sweep.policy_area(NumericsPolicy((), default=SEG1), paths))
    assert 0.0 < rep["area_reduction"] < 1.0
    assert rep["compute_scale"] < 1.0  # 1 of 6 MXU passes


def test_session_save_policy_round_trips(tmp_path):
    pol = NumericsPolicy((PolicyRule("blocks.*", SEG1),))
    s = Session("qwen3-4b", policy=pol)
    out = tmp_path / "out.json"
    s.save_policy(str(out))
    assert NumericsPolicy.from_json(out.read_text()) == pol


# ---------------------------------------------------------------------------
# generation (the serve loop)
# ---------------------------------------------------------------------------

def test_session_generate_deterministic_and_policy_equivalence():
    pol = NumericsPolicy((), default=SEG1)
    a = Session("qwen3-4b", policy="segmented1").generate(
        batch=1, prompt_len=4, gen_len=2)
    b = Session("qwen3-4b", policy=pol).generate(
        batch=1, prompt_len=4, gen_len=2)
    assert isinstance(a, GenerateResult)
    assert a.tokens.shape == (1, 2) and a.tokens.dtype == np.int32
    # a default-only policy == the same global config, token-for-token
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert a.tokens_per_s > 0


def test_session_family_guards():
    with pytest.raises(SessionError, match="generate"):
        Session("qwen3-4b").apply(np.zeros((1, 8, 8, 3), np.float32))
    cfg = resnet.ResNetConfig(widths=(8, 16), blocks=(1, 1))
    with pytest.raises(SessionError, match="from_resnet"):
        _ = Session(cfg).params
    with pytest.raises(SessionError, match="no launch shapes"):
        Session(cfg).dryrun("train_4k")
    with pytest.raises(SessionError, match="unknown dryrun shape"):
        Session("qwen3-4b").dryrun("train4k")


def test_session_generate_reuses_compiled_functions():
    """Repeated generate() on one Session must reuse the jitted prefill/
    decode (per-(config, max_len) cache) instead of recompiling."""
    s = Session("qwen3-4b", policy="segmented1")
    s.generate(batch=1, prompt_len=4, gen_len=2)
    assert len(s._jit_cache) == 1
    s.generate(batch=1, prompt_len=4, gen_len=2)
    assert len(s._jit_cache) == 1          # same key: no new closures
    s.generate(batch=1, prompt_len=4, gen_len=3)
    assert len(s._jit_cache) == 2          # new max_len: new entry


@pytest.fixture(scope="module")
def gen_session():
    """One compiled session shared by the generate(prompts=...) tests."""
    return Session("qwen3-4b", policy="segmented1")


def test_session_generate_explicit_prompts_win_over_shape_args(gen_session,
                                                               rng):
    """``prompts`` overrides batch/prompt_len (taken from the array), and
    a plain nested list is accepted."""
    P = rng.integers(0, gen_session.config.vocab, (3, 6))
    res = gen_session.generate(prompts=P, gen_len=2, batch=99, prompt_len=99)
    assert res.tokens.shape == (3, 2)
    res_list = gen_session.generate(prompts=P.tolist(), gen_len=2)
    np.testing.assert_array_equal(res.tokens, res_list.tokens)


def test_session_generate_left_padded_prompts_pinned(gen_session, rng):
    """Ragged-intent batches are served LEFT-PADDED by the caller, and the
    pad is an ordinary vocab token: no pad masking, so each row's tokens
    equal a solo run of the same literal padded row (rows are
    independent).  Pinned: callers who pad must pad the solo reference
    identically to reproduce batched results."""
    vocab = gen_session.config.vocab
    short = rng.integers(1, vocab, 3)
    long = rng.integers(1, vocab, 6)
    P = np.stack([np.concatenate([np.zeros(3, np.int64), short]), long])
    batched = gen_session.generate(prompts=P, gen_len=3)
    for row in range(2):
        solo = gen_session.generate(prompts=P[row:row + 1], gen_len=3)
        np.testing.assert_array_equal(batched.tokens[row], solo.tokens[0])


def test_session_generate_result_stats_contract(gen_session, rng):
    P = rng.integers(0, gen_session.config.vocab, (2, 4))
    res = gen_session.generate(prompts=P, gen_len=4)
    assert isinstance(res, GenerateResult)
    assert res.tokens.shape == (2, 4) and res.tokens.dtype == np.int32
    assert (0 <= res.tokens).all() and (res.tokens <
                                        gen_session.config.vocab).all()
    assert res.seconds > 0
    assert res.tokens_per_s == pytest.approx(2 * 4 / res.seconds)
    # without eos_id every row is full length
    np.testing.assert_array_equal(res.gen_lengths, [4, 4])


def test_session_generate_eos_bit_transparent(gen_session, rng):
    """EOS stopping never changes a row's pre-EOS tokens: rows that hit
    the stop token match the no-eos run up to (and including) the EOS and
    come back pinned to it after; rows that never hit it are identical
    end to end."""
    P = rng.integers(0, gen_session.config.vocab, (3, 6))
    base = gen_session.generate(prompts=P, gen_len=8)
    eos = int(base.tokens[0, 2])  # some token row 0 emits mid-stream
    res = gen_session.generate(prompts=P, gen_len=8, eos_id=eos)
    assert res.tokens.shape == base.tokens.shape  # padded, shape-stable
    stopped = 0
    for b in range(3):
        row = base.tokens[b]
        hits = np.nonzero(row == eos)[0]
        if hits.size:
            n = hits[0] + 1
            stopped += 1
            np.testing.assert_array_equal(res.tokens[b, :n], row[:n])
            assert (res.tokens[b, n:] == eos).all()
            assert res.gen_lengths[b] == n
        else:
            np.testing.assert_array_equal(res.tokens[b], row)
            assert res.gen_lengths[b] == 8
    assert stopped >= 1  # eos chosen from an emitted token: row 0 stops
    assert res.tokens_per_s == pytest.approx(
        int(res.gen_lengths.sum()) / res.seconds)


def test_session_generate_eos_all_rows_exit_early(gen_session, rng):
    """When every row has finished the decode loop stops instead of
    burning the remaining steps; output is still (batch, gen_len)."""
    P = rng.integers(0, gen_session.config.vocab, (1, 6))
    base = gen_session.generate(prompts=P, gen_len=8)
    eos = int(base.tokens[0, 0])  # very first emitted token
    res = gen_session.generate(prompts=P, gen_len=8, eos_id=eos)
    assert res.tokens.shape == (1, 8)
    assert res.gen_lengths[0] == 1
    assert (res.tokens[0] == eos).all()


# ---------------------------------------------------------------------------
# resnet sessions + auto-configuration (the sweep)
# ---------------------------------------------------------------------------

def _tiny_resnet(seed=0):
    cfg = resnet.ResNetConfig(widths=(8, 16), blocks=(1, 1))
    pp, state = resnet.init(cfg, jax.random.PRNGKey(seed))
    params, _ = unzip(pp)
    rng = np.random.default_rng(seed)
    images = jnp.asarray(rng.standard_normal((4, 8, 8, 3)), jnp.float32)
    return cfg, params, state, images


def test_session_resnet_apply_and_replace():
    cfg, params, state, images = _tiny_resnet()
    sess = Session.from_resnet(cfg, params, state)
    ref = np.asarray(sess.apply(images))
    approx = np.asarray(sess.replace(policy=SEG1).apply(images))
    assert np.isfinite(approx).all()
    assert not np.allclose(ref, approx)
    # replace() didn't mutate the original session
    np.testing.assert_array_equal(ref, np.asarray(sess.apply(images)))


def test_session_resnet_auto_configure_adopts_policy():
    cfg, params, state, images = _tiny_resnet()
    sess = Session.from_resnet(cfg, params, state)
    ref = np.asarray(sess.apply(images), np.float64)
    budget = 5e-3
    res = sess.auto_configure(budget, calib=images,
                              candidates=[("segmented-1", SEG1),
                                          ("segmented-3", SEG3)],
                              method="greedy")
    assert res.error <= budget
    assert res.area_um2 < res.baseline_area_um2
    # the session now serves under the emitted policy
    assert sess.config.numerics == res.policy
    measured = mred(np.asarray(sess.apply(images)), ref)
    assert measured <= budget
    with pytest.raises(SessionError, match="calibration image batch"):
        Session.from_resnet(cfg, params, state).auto_configure(budget)


# ---------------------------------------------------------------------------
# serving tiers (the serve-loop CLI's --tiers spec)
# ---------------------------------------------------------------------------

def test_parse_tiers_spec():
    from repro.session import parse_tiers

    tiers = parse_tiers("premium:exact,bulk:segmented1")
    assert [(t.name, t.policy, t.priority) for t in tiers] == \
        [("premium", "exact", 0), ("bulk", "segmented1", 1)]
    with pytest.raises(SessionError, match="tier spec"):
        parse_tiers("premium")          # missing :policy
    with pytest.raises(SessionError, match="tier spec"):
        parse_tiers("")
    with pytest.raises(SessionError, match="duplicate tier"):
        parse_tiers("a:exact,a:segmented1")


# ---------------------------------------------------------------------------
# serve CLI: thin wrapper + one-line errors, non-zero exit
# ---------------------------------------------------------------------------

@pytest.fixture
def restore_compile_cache_dir():
    """serve.main points the persistent compile cache at the checkout;
    these in-process calls compile nothing, so restoring the setting
    keeps later tests in this worker off the cache."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_serve_cli_missing_policy_file_exits_nonzero(
        capsys, restore_compile_cache_dir):
    from repro.launch import serve

    rc = serve.main(["--policy", "/does/not/exist.json", "--batch", "1",
                     "--gen-len", "2"])
    assert rc != 0
    err = capsys.readouterr().err.strip()
    assert err.startswith("error:") and "\n" not in err
    assert "cannot read policy file" in err


def test_serve_cli_malformed_policy_file_exits_nonzero(
        tmp_path, capsys, restore_compile_cache_dir):
    from repro.launch import serve

    bad = tmp_path / "bad.json"
    bad.write_text("{definitely: not json")
    rc = serve.main(["--policy", str(bad), "--batch", "1", "--gen-len", "2"])
    assert rc != 0
    err = capsys.readouterr().err.strip()
    assert err.startswith("error:") and "\n" not in err
    assert "invalid policy JSON" in err


@pytest.mark.slow
def test_serve_function_routes_through_session():
    """serve() == Session.generate, token-for-token (same arch/seed/preset)."""
    from repro.launch.serve import serve

    toks = serve(batch=1, prompt_len=8, gen_len=3, numerics="segmented1")
    res = Session("qwen3-4b", policy="segmented1").generate(
        batch=1, prompt_len=8, gen_len=3)
    np.testing.assert_array_equal(toks, res.tokens)
