"""The comparison that decides ``correct``.

Once the window has closed and the program is freed, a sample of the
requests the program finished (the longest, then the longest of each tier,
then the longest of each decode row of each tier not yet covered, then
others drawn from the seed, up to the limit file's ``requests``) is run
through the float32 reference over its prompt and its served tokens.  At each position where the program
served a token, the gap by which the reference's logit for that token lies
below the reference's best logit is read in units of the standard
deviation of the reference's logits there.  The widest such gap is the
number compared (``logit_gap_max_sd``); ``tokens_compared`` guards against
a run that served too little to be checked.

The control is judged by the same check: at the same positions it serves
the token that the reference computed with fp8 operands puts first, and
the widest gap of those tokens is held to the same limit.  Its verdict has
to come out as not correct.
"""
from __future__ import annotations

import numpy as np

from chipbench import model, reference


def same_tree(spec, params) -> None:
    """The seeded tree has the program's layout, shapes and dtypes."""
    import jax

    from repro.models import transformer
    from repro.models.layers import unzip

    cfg = model.arch_config(spec)
    want = jax.eval_shape(lambda k: unzip(transformer.init(cfg, k))[0],
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{spec.name}: seeded weights do not match the "
                         f"program's parameter tree")


def sample(served: list, seed: int, k: int) -> list:
    """Up to ``k`` finished requests ``(planned, tokens, slot)``: the
    longest overall, per tier, and per decode row of each tier, then the
    rest drawn from the seed."""
    size = lambda pt: pt[0].prompt.size + len(pt[1])
    order = sorted(served, key=lambda pt: (-size(pt), pt[0].id))
    pick = []
    for key in (lambda pt: 0, lambda pt: pt[0].tier,
                lambda pt: (pt[0].tier, pt[2])):
        for pt in order:
            if pt not in pick and all(key(pt) != key(q) for q in pick):
                pick.append(pt)
    rest = [pt for pt in order if pt not in pick]
    rng = np.random.default_rng(int(seed) + 1)
    rest = [rest[i] for i in rng.permutation(len(rest))]
    return (pick + rest)[:k]


def _positions(planned, tokens, S: int):
    """The sequence the reference reads (prompt then served tokens but the
    last, zero-padded to ``S``) and the rows at which each served token was
    chosen."""
    L = planned.prompt.size
    seq = np.zeros(S, np.int32)
    seq[:L] = planned.prompt
    seq[L:L + len(tokens) - 1] = tokens[:-1]
    rows = np.arange(L - 1, L - 1 + len(tokens))
    return seq, rows


def _seq_len(mix: dict) -> int:
    q = reference.Q_BLOCK
    return -(-mix["engine"]["max_len"] // q) * q


def _rows_len(mix: dict) -> int:
    return mix["output_tokens"]["max"]


def gaps(spec, w, picked: list, mix: dict, quant=None) -> list:
    """Per picked request: (reference gap of each served token, reference
    gap of the token the ``quant`` forward puts first or None), in
    standard deviations of the reference's logits."""
    import jax.numpy as jnp

    S, R = _seq_len(mix), _rows_len(mix)
    out = []
    for planned, tokens, _ in picked:
        seq, rows = _positions(planned, tokens, S)
        pad = np.zeros(R, np.int32)
        pad[:rows.size] = rows
        seq = jnp.asarray(seq)
        ref = reference.logits(spec, w, reference.hidden(spec, w, seq)[pad])
        best = jnp.max(ref, axis=-1)
        sd = jnp.std(ref, axis=-1)
        served = np.zeros(R, np.int32)
        served[:rows.size] = tokens
        got = jnp.take_along_axis(ref, jnp.asarray(served)[:, None], -1)[:, 0]
        g_prog = np.asarray((best - got) / sd)[:rows.size]
        g_ctrl = None
        if quant is not None:
            low = reference.logits(
                spec, w, reference.hidden(spec, w, seq, quant)[pad], quant)
            top = jnp.argmax(low, axis=-1)
            got = jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
            g_ctrl = np.asarray((best - got) / sd)[:rows.size]
        out.append((g_prog, g_ctrl))
    return out


def meets(widest, n_tok: int, limits: dict) -> bool:
    """The check: enough served tokens compared, none of them further below
    the reference's best than the limit."""
    return (widest is not None
            and n_tok >= limits["tokens_compared_min"]
            and widest <= limits["logit_gap_max_sd"])


def compare(spec, seed: int, served: list, limits: dict, mix: dict,
            say=lambda m: None, control: bool = False):
    """(checks, correct, control) for the requests the program finished.
    ``control`` is None, or with ``control`` the verdict of the same check
    on the tokens the fp8 control puts first at the same positions:
    ``{"logit_gap_max_sd": widest gap, "correct": bool}``."""
    picked = sample(served, seed, limits["requests"])
    n_tok = sum(len(t) for _, t, _ in picked)
    checks = {"tokens_compared": {"value": n_tok,
                                  "min": limits["tokens_compared_min"]}}
    widest, ctrl = None, None
    if picked:
        w = model.make_weights(spec, seed)
        per = gaps(spec, w, picked, mix, "fp8" if control else None)
        del w
        widest = float(max(g.max() for g, _ in per))
        if control:
            ctrl = float(max(c.max() for _, c in per))
            say(f"control (fp8 operands): widest gap {ctrl:.5f} sd, "
                f"correct {meets(ctrl, n_tok, limits)}")
        say(f"compared {len(picked)} requests "
            f"({', '.join(f'{p.id}:{p.tier}/{s}:{p.prompt.size}+{len(t)}' for p, t, s in picked)}), "
            f"{n_tok} tokens, {len({x for _, t, _ in picked for x in t})} "
            f"distinct; widest gap {widest:.5f} sd")
    checks["logit_gap_max_sd"] = {"value": widest,
                                  "max": limits["logit_gap_max_sd"]}
    verdict = None
    if control:
        verdict = {"logit_gap_max_sd": ctrl,
                   "correct": meets(ctrl, n_tok, limits)}
    return checks, meets(widest, n_tok, limits), verdict
