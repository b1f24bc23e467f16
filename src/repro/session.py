"""Unified Session facade — one spec for every entry point.

``serve``, ``dryrun``, the benchmark drivers and the auto-configuration
sweep all need the same tuple: an architecture, a numerics policy, a
kernel backend, and (for compiled dry-runs) a mesh.  Before this module
each entry point re-assembled that tuple with its own ad-hoc signature;
:class:`Session` owns it once:

>>> from repro.session import Session
>>> s = Session("qwen3-4b", policy="segmented1")
>>> out = s.generate(batch=2, prompt_len=16, gen_len=8)   # serve loop
>>> s.ppa_report()["area_reduction"]                      # Table II roll-up
>>> res = s.auto_configure(budget=1e-2)                   # proxy sweep
>>> s.save_policy("policy.json")

``policy`` accepts a :class:`~repro.core.policy.NumericsPolicy`, a plain
:class:`~repro.core.numerics.NumericsConfig`, a preset name (``exact`` /
``segmented1|2|3``), or a path to a policy JSON file (the ``serve
--policy`` wire format); malformed files raise :class:`SessionError` with
a one-line message instead of a traceback.

The module doubles as the unified CLI (the sweep CLI of the repo):

    python -m repro.session generate       --arch qwen3-4b --policy p.json
    python -m repro.session auto-configure --arch qwen3-4b --budget 1e-2 --out p.json
    python -m repro.session ppa            --arch qwen3-4b --policy p.json
    python -m repro.session dryrun         --arch qwen3-4b --shape train_4k

``repro.launch.serve``, ``repro.launch.dryrun`` and
``benchmarks/table4_resnet.py`` are thin wrappers over Session.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core.numerics import NumericsConfig
from repro.core.policy import Numerics, NumericsPolicy, is_policy
from repro.configs.base import ArchConfig

__all__ = ["GenerateResult", "Session", "SessionError", "build_parser",
           "load_policy", "print_ppa_report"]


class SessionError(RuntimeError):
    """A session-level configuration error with a one-line message."""


# the fast split-float ladder — the default auto-configure candidate set
# (CPU-cheap calibration; pass candidates="emulated" for the bit-level
# Pareto-frontier designs of repro.core.sweep.pareto_candidates).  The
# "auto" backend runs the Pallas kernel on a TPU and the XLA reference
# everywhere else.
SEGMENTED_CANDIDATES: Tuple[Tuple[str, NumericsConfig], ...] = (
    ("segmented-1", NumericsConfig(mode="segmented", seg_passes=1, backend="auto")),
    ("segmented-2", NumericsConfig(mode="segmented", seg_passes=2, backend="auto")),
    ("segmented-3", NumericsConfig(mode="segmented", seg_passes=3, backend="auto")),
)

# "exact" keeps the arch's own numerics (exact by default); segmented
# presets are the same ladder the auto-configurer sweeps
_PRESETS = {"exact": None,
            **{name.replace("-", ""): cfg
               for name, cfg in SEGMENTED_CANDIDATES}}


def load_policy(path: str) -> NumericsPolicy:
    """Load a NumericsPolicy from a JSON file with one-line errors."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        raise SessionError(
            f"cannot read policy file {path!r}: {e.strerror or e}") from e
    try:
        return NumericsPolicy.from_json(text)
    except (json.JSONDecodeError, ValueError, KeyError, TypeError) as e:
        raise SessionError(f"invalid policy JSON in {path!r}: {e}") from e


def _coerce_numerics(policy) -> Optional[Numerics]:
    """policy arg -> Numerics override (None = keep the arch's own)."""
    if policy is None or isinstance(policy, (NumericsConfig, NumericsPolicy)):
        return policy
    if is_policy(policy):  # ScopedPolicy view: prefixed, not servable as-is
        raise SessionError(
            "a ScopedPolicy view cannot configure a session — pass the root "
            "NumericsPolicy (views are created per layer during resolution)")
    if isinstance(policy, str):
        if policy in _PRESETS:
            return _PRESETS[policy]
        return load_policy(policy)
    raise SessionError(
        f"unsupported policy spec {policy!r}: expected a NumericsConfig, "
        f"NumericsPolicy, preset name ({'/'.join(_PRESETS)}) or a JSON path")


def _with_backend(numerics: Numerics, backend: str) -> Numerics:
    """Force the kernel backend on every config a Numerics can resolve to."""
    if isinstance(numerics, NumericsConfig):
        return dataclasses.replace(numerics, backend=backend)
    d = numerics.to_dict()
    d["default"]["backend"] = backend
    for r in d["rules"]:
        r["config"]["backend"] = backend
    return NumericsPolicy.from_dict(d)


@dataclasses.dataclass(frozen=True)
class GenerateResult:
    tokens: np.ndarray        # (batch, gen_len) int32 greedy continuations
    seconds: float
    tokens_per_s: float
    # per-row emitted-token counts (EOS included).  Rows that hit the EOS
    # stop token have their remaining columns pinned to eos_id; without
    # eos_id every row is full-length.
    gen_lengths: Optional[np.ndarray] = None


class Session:
    """(arch, policy, backend, mesh) + params — the one public spec.

    ``arch`` is an arch-id string from ``repro.configs`` (reduced to the
    CPU-sized config unless ``reduced=False``), a ready
    :class:`~repro.configs.base.ArchConfig`, or a
    :class:`~repro.models.resnet.ResNetConfig` (see :meth:`from_resnet`).
    ``mesh`` is carried for the dry-run path (``multi`` selects the
    2x16x16 multi-pod mesh; anything else the single-pod 16x16).
    """

    def __init__(self, arch, policy=None, backend: Optional[str] = None,
                 mesh: Optional[str] = None, *, seed: int = 0,
                 reduced: bool = True, params=None, state=None, tune=None):
        from repro.models import resnet as resnet_mod

        if isinstance(arch, str):
            from repro.configs import get_arch

            try:
                base = get_arch(arch)
            except ValueError as e:
                raise SessionError(str(e)) from e
            self.arch_id = arch
            self._base_cfg = base.reduced() if reduced else base
            self._family = "lm"
        elif isinstance(arch, ArchConfig):
            self.arch_id = arch.arch_id
            self._base_cfg = arch
            self._family = "lm"
        elif isinstance(arch, resnet_mod.ResNetConfig):
            self.arch_id = "resnet18"
            self._base_cfg = arch
            self._family = "resnet"
        else:
            raise SessionError(
                f"unsupported arch spec {arch!r}: expected an arch id, "
                f"ArchConfig or ResNetConfig")
        self.backend = backend
        self.mesh = mesh
        self.seed = seed
        self._numerics_override = _coerce_numerics(policy)
        self._params = params
        self._state = state  # resnet batchnorm state
        self._jit_cache = {}  # (config, max_len) -> (prefill, decode)
        # measured kernel-tuning artifact (path or TuningTable); activation
        # is process-wide — the dispatch lookups it feeds are module-level,
        # exactly like the static tables they replace
        self._tune = tune
        if tune is not None:
            from repro.kernels import autotune

            try:
                autotune.activate(tune)
            except autotune.TuneError as e:
                raise SessionError(str(e)) from e

    # -- configuration ------------------------------------------------------

    @property
    def numerics(self) -> Numerics:
        """The effective Numerics (override > arch default > backend)."""
        num = (self._numerics_override
               if self._numerics_override is not None
               else self._base_cfg.numerics)
        if self.backend is not None:
            num = _with_backend(num, self.backend)
        return num

    @property
    def config(self):
        """The arch config with this session's numerics applied."""
        return dataclasses.replace(self._base_cfg, numerics=self.numerics)

    @property
    def is_policy(self) -> bool:
        return is_policy(self.numerics)

    def replace(self, **kw) -> "Session":
        """A new Session with fields replaced (policy/backend/mesh/seed/
        params/state/tune); params/state are shared unless overridden."""
        args = dict(policy=self._numerics_override, backend=self.backend,
                    mesh=self.mesh, seed=self.seed, params=self._params,
                    state=self._state, tune=self._tune)
        unknown = set(kw) - set(args)
        if unknown:
            raise SessionError(
                f"unknown Session.replace field(s) {sorted(unknown)}; "
                f"expected a subset of {sorted(args)}")
        args.update(kw)
        return Session(self._base_cfg, args["policy"], args["backend"],
                       args["mesh"], seed=args["seed"],
                       params=args["params"], state=args["state"],
                       tune=args["tune"])

    # -- parameters ---------------------------------------------------------

    @property
    def params(self):
        """Model parameters (lazily initialized for the LM zoo)."""
        if self._params is None:
            if self._family != "lm":
                raise SessionError(
                    "resnet sessions need trained params: use "
                    "Session.from_resnet(cfg, params, state)")
            import jax

            from repro.models import transformer
            from repro.models.layers import unzip

            pp = transformer.init(self.config, jax.random.PRNGKey(self.seed))
            self._params, _ = unzip(pp)
        return self._params

    @classmethod
    def from_resnet(cls, cfg, params, state, policy=None,
                    backend: Optional[str] = None, seed: int = 0) -> "Session":
        """Session over a trained ResNet: ``cfg`` is a ResNetConfig,
        ``params``/``state`` the trained trees (``repro.models.resnet``)."""
        return cls(cfg, policy, backend, seed=seed, params=params,
                   state=state)

    @classmethod
    def from_pretrained(cls, family: str, path, policy=None,
                        backend: Optional[str] = None,
                        mesh: Optional[str] = None, *, cfg=None,
                        reduced: bool = True, unknown: str = "error",
                        cast: bool = True, seed: int = 0,
                        tune=None) -> "Session":
        """A Session over real pretrained weights (``repro.compat``).

        ``family`` names a registered checkpoint converter (``qwen3-4b``,
        ``whisper-tiny``, ``resnet18``); ``path`` is a safetensors file,
        a sharded ``*.safetensors.index.json`` (or a directory holding
        either), or a torch pickle.  The architecture comes from ``cfg``
        when given, else the checkpoint's ``repro.config`` metadata, else
        the registered arch (``reduced`` picking the CPU-sized variant).
        ``unknown``/``cast`` are forwarded to
        :func:`repro.compat.load_pretrained`; interop failures surface as
        one-line :class:`repro.compat.CompatError`\\ s.
        """
        from repro import compat

        loaded = compat.load_pretrained(family, path, cfg=cfg,
                                        reduced=reduced, unknown=unknown,
                                        cast=cast)
        if loaded.kind == "resnet":
            return cls(loaded.cfg, policy, backend, seed=seed,
                       params=loaded.params, state=loaded.state, tune=tune)
        return cls(loaded.cfg, policy, backend, mesh, seed=seed,
                   params=loaded.params, tune=tune)

    def export(self, path) -> None:
        """Write this session's params (+ ResNet bn state) as a single
        safetensors checkpoint in the family's foreign naming scheme —
        the exact inverse of :meth:`from_pretrained`, so an
        export/reload round trip is bit-exact."""
        from repro import compat

        foreign, meta = compat.export_pretrained(
            self.arch_id, self._base_cfg, self.params, self._state)
        compat.write_safetensors(path, foreign, meta)

    # -- layer enumeration / PPA -------------------------------------------

    def layer_paths(self) -> list:
        if self._family == "resnet":
            from repro.models import resnet

            return resnet.layer_paths(self._base_cfg)
        from repro.models import transformer

        return transformer.layer_paths(self.config)

    def layer_path_counts(self) -> Mapping[str, int]:
        if self._family == "resnet":
            return {}
        from repro.models import transformer

        return transformer.layer_path_counts(self.config)

    def ppa_report(self) -> dict:
        """Modeled PPA of this session's numerics over every call site:
        the Table II area/power roll-up plus the MXU-pass roofline scale
        (``repro.launch.hlo_analysis.policy_ppa_summary``)."""
        from repro.launch import hlo_analysis

        num = self.numerics
        policy = (num if isinstance(num, NumericsPolicy)
                  else NumericsPolicy((), default=num))
        return hlo_analysis.policy_ppa_summary(
            policy, self.layer_paths(), counts=self.layer_path_counts())

    def save_policy(self, path: str) -> None:
        num = self.numerics
        policy = (num if isinstance(num, NumericsPolicy)
                  else NumericsPolicy((), default=num))
        with open(path, "w") as f:
            f.write(policy.to_json())

    # -- forward / generation ----------------------------------------------

    def apply(self, images):
        """ResNet inference under the session numerics -> logits."""
        if self._family != "resnet":
            raise SessionError("apply(images) is the ResNet entry point; "
                               "use generate() for the LM zoo")
        from repro.models import resnet

        logits, _ = resnet.apply(self.params, self._state, images,
                                 self.config, train=False)
        return logits

    def generate(self, batch: int = 4, prompt_len: int = 32,
                 gen_len: int = 16, prompts=None,
                 eos_id: Optional[int] = None) -> GenerateResult:
        """Batched prefill + greedy decode loop (the serve driver).

        ``prompts`` (batch, prompt_len) int32 overrides the seeded random
        prompts.  Returns the generated tokens plus wall-clock stats.

        ``eos_id`` enables stop-token handling: a per-row finished mask
        tracks rows that emitted the token, the loop exits early once
        every row has, and finished rows' remaining columns come back
        pinned to ``eos_id`` (``gen_lengths`` carries the true per-row
        counts, EOS included).  Stopping is bit-transparent: the tokens a
        row emits before its EOS are identical with and without
        ``eos_id``, because unfinished rows keep seeing exactly the same
        batched decode steps.
        """
        if self._family != "lm":
            raise SessionError("generate() is the LM entry point; use "
                               "apply(images) for ResNet sessions")
        import jax
        import jax.numpy as jnp

        from repro.models import transformer

        cfg = self.config
        params = self.params
        if prompts is None:
            rng = np.random.default_rng(self.seed)
            prompts = jnp.asarray(
                rng.integers(0, cfg.vocab, (batch, prompt_len)), jnp.int32)
        else:
            prompts = jnp.asarray(prompts, jnp.int32)
            batch, prompt_len = prompts.shape
        max_len = prompt_len + gen_len

        # jitted callables are cached per (config, max_len) so repeated
        # generate() calls on one Session reuse compiled code instead of
        # paying two fresh XLA compilations each time (jax.jit caches per
        # function object; the config is closed over, so a policy/backend
        # change via replace() naturally gets its own entry)
        key = (cfg, max_len)
        if key not in self._jit_cache:
            self._jit_cache[key] = (
                jax.jit(lambda p, b: transformer.prefill(p, cfg, b,
                                                         max_len=max_len)),
                jax.jit(lambda p, tok, st, pos: transformer.decode_step(
                    p, cfg, {"token": tok}, st, pos)),
            )
        prefill, decode = self._jit_cache[key]

        t0 = time.perf_counter()
        logits, state = prefill(params, {"tokens": prompts})
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out = [tok]
        # the EOS mask lives on the host (it gates the python loop); the
        # decode itself always advances the full batch, so a row's tokens
        # are unchanged by other rows finishing
        finished = (np.asarray(tok)[:, 0] == eos_id
                    if eos_id is not None else None)
        for i in range(gen_len - 1):
            if finished is not None and finished.all():
                break
            logits, state = decode(params, tok, state, jnp.int32(prompt_len + i))
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            out.append(tok)
            if finished is not None:
                finished = finished | (np.asarray(tok)[:, 0] == eos_id)
        jax.block_until_ready(tok)
        dt = time.perf_counter() - t0
        gen = np.asarray(jnp.concatenate(out, axis=1))
        if eos_id is None:
            return GenerateResult(tokens=gen, seconds=dt,
                                  tokens_per_s=batch * gen_len / dt,
                                  gen_lengths=np.full(batch, gen_len,
                                                      np.int64))
        emitted = gen.shape[1]
        lengths = np.full(batch, gen_len, np.int64)
        full = np.full((batch, gen_len), eos_id, np.int32)
        full[:, :emitted] = gen
        for b in range(batch):
            hits = np.nonzero(gen[b] == eos_id)[0]
            if hits.size:
                lengths[b] = hits[0] + 1
                full[b, hits[0] + 1:] = eos_id
        return GenerateResult(tokens=full, seconds=dt,
                              tokens_per_s=int(lengths.sum()) / dt,
                              gen_lengths=lengths)

    # -- serving (continuous batching) -------------------------------------

    def serving_engine(self, tiers=None, *, slots: int = 4,
                       max_len: int = 64, page_size=None, pages=None,
                       prefill_chunk=None, clock=None, aging=None,
                       prefill_cache=None):
        """A continuous-batching :class:`repro.serving.Engine` over this
        session's resident weights: one paged KV pool + one resident
        compiled decode per accuracy tier, requests joining mid-decode
        (design: ``docs/serving.md``).

        ``tiers`` is a sequence of :class:`repro.serving.TierSpec`
        (default: the premium/standard/bulk SLA ladder); each tier's
        ``policy`` goes through the same coercion as ``Session(policy=...)``.
        ``page_size`` sets the KV page granularity (default 16 tokens) and
        ``pages`` the physical pool per tier (default ``slots *
        ceil(max_len / page_size)``); a request reserves only the pages
        its own ``prompt + max_new - 1`` positions need.
        ``prefill_chunk`` (default 32) bounds the prompt tokens prefilled
        per engine step, so long prompts interleave with in-flight
        decodes; ``prefill_cache`` bounds each lane's compiled
        prefill-shape cache (LRU; default 32 shapes).  Continuous
        batching never changes a request's numerics — every request's
        tokens are bit-identical to a solo :meth:`generate` of the same
        prompt under that tier's policy.
        """
        if self._family != "lm":
            raise SessionError("serving_engine() is the LM entry point; "
                               "ResNet sessions have no decode loop")
        from repro.serving import DEFAULT_TIERS, Engine

        tiers = DEFAULT_TIERS if tiers is None else tuple(tiers)
        return Engine.from_session(self, tiers, slots=slots, max_len=max_len,
                                   page_size=page_size, pages=pages,
                                   prefill_chunk=prefill_chunk,
                                   clock=clock, aging=aging,
                                   prefill_cache=prefill_cache)

    # -- auto-configuration (the sweep) ------------------------------------

    def auto_configure(self, budget: float, calib=None, candidates=None,
                       method: str = "proxy", default=None,
                       verbose: bool = False):
        """Budget-driven per-layer numerics selection over this session's
        network; adopts the emitted policy as the session numerics.

        ``calib`` is the calibration input — a token batch dict
        (``{"tokens": ...}``, plus ``"enc_embeds"`` for encoder-decoder
        archs) for the LM zoo (default: seeded random tokens, and seeded
        random encoder embeddings when the arch has an encoder), an image
        array for ResNet sessions.  ``candidates`` is a ``(name,
        NumericsConfig)`` list, ``"segmented"`` (default: the split-float
        ladder) or ``"emulated"`` (bit-level Pareto designs).

        ``method="proxy"`` (default) fits the gain-aware composed-error
        model from ONE instrumented pass (``repro.core.sensitivity``);
        scanned decoder segments and the whisper-style encoder unroll
        transparently during that pass, so every site —
        ``encoder.blocks.*`` included — is visible to the calibration
        tap.  Returns the :class:`repro.core.sweep.AutoConfigResult`.
        """
        import jax.numpy as jnp

        from repro.core import sweep
        from repro.core.metrics import mred

        if candidates is None or candidates == "segmented":
            cand: Optional[Sequence] = list(SEGMENTED_CANDIDATES)
        elif candidates == "emulated":
            cand = None  # sweep's default: emulated Pareto frontier
        else:
            cand = list(candidates)

        if self._family == "resnet":
            from repro.models import resnet

            if calib is None:
                raise SessionError(
                    "resnet auto_configure needs a calibration image batch "
                    "(calib=images)")
            images = jnp.asarray(calib)
            base_cfg = dataclasses.replace(
                self._base_cfg,
                numerics=NumericsConfig(mode="exact", compute_dtype="float32"))
            ref, _ = resnet.apply(self.params, self._state, images, base_cfg,
                                  train=False)
            ref = np.asarray(ref, np.float64)

            def eval_fn(policy):
                acfg = dataclasses.replace(base_cfg, numerics=policy)
                logits, _ = resnet.apply(self.params, self._state, images,
                                         acfg, train=False)
                return mred(np.asarray(logits), ref)

            default = default or NumericsConfig(mode="exact",
                                                compute_dtype="float32")
        else:
            from repro.models import transformer

            cfg = self.config
            if calib is None:
                rng = np.random.default_rng(self.seed)
                calib = {"tokens": jnp.asarray(
                    rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)}
                if cfg.encoder_layers:
                    # encoder-decoder archs also need encoder inputs so the
                    # calibration pass reaches the encoder.blocks.* sites
                    # (cfg.enc_len itself only sizes serving caches, which
                    # the train-mode calibration forward never allocates)
                    enc_len = min(cfg.enc_len, 16)
                    calib["enc_embeds"] = jnp.asarray(rng.standard_normal(
                        (2, enc_len, cfg.d_model)), jnp.float32)
            # the default must match the network's own exact numerics (bf16
            # for the LM zoo) so the baseline itself reads as zero error
            default = default or NumericsConfig(mode="exact")
            base_cfg = dataclasses.replace(cfg, numerics=default)
            params = self.params
            hidden, _, _ = transformer.backbone(params, base_cfg, calib,
                                                mode="train")
            ref = np.asarray(transformer.logits_fn(params, base_cfg, hidden),
                             np.float64)

            def eval_fn(policy):
                pcfg = dataclasses.replace(cfg, numerics=policy)
                h, _, _ = transformer.backbone(params, pcfg, calib,
                                               mode="train")
                return mred(
                    np.asarray(transformer.logits_fn(params, pcfg, h)), ref)

        res = sweep.auto_configure(eval_fn, self.layer_paths(), budget,
                                   candidates=cand, default=default,
                                   method=method, verbose=verbose)
        self._numerics_override = res.policy
        return res

    # -- compiled dry-run ---------------------------------------------------

    def dryrun(self, shape: str, multi_pod: Optional[bool] = None) -> dict:
        """Lower + compile one (arch x shape x mesh) cell and return the
        roofline/memory record (``repro.launch.dryrun``).  Requires the
        512-fake-device environment the dryrun CLI sets up — use
        ``python -m repro.launch.dryrun`` (or ``python -m repro.session
        dryrun``) from a fresh process.
        """
        if self._family != "lm":
            raise SessionError("dryrun() is the LM entry point; ResNet "
                               "sessions have no launch shapes")
        from repro.launch import specs

        if shape not in specs.SHAPES:
            raise SessionError(f"unknown dryrun shape {shape!r}; expected "
                               f"one of {sorted(specs.SHAPES)}")
        from repro.launch import dryrun as dryrun_mod

        if multi_pod is None:
            multi_pod = self.mesh == "multi"
        try:
            return dryrun_mod.lower_session_cell(self, shape, multi_pod)
        except RuntimeError as e:
            if "device" not in str(e):
                raise
            # mesh construction needs the fake-device env the dryrun CLI
            # sets before jax loads; in-process callers must preset it
            raise SessionError(
                f"{e} (python -m repro.session imports jax before the "
                f"dryrun module can set it — run with XLA_FLAGS="
                f"--xla_force_host_platform_device_count=512, or use "
                f"python -m repro.launch.dryrun)") from e


# ---------------------------------------------------------------------------
# the unified CLI (generate / auto-configure / ppa / dryrun)
# ---------------------------------------------------------------------------

def _add_common(ap):
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--policy", default=None,
                    help="NumericsPolicy JSON file, or a preset "
                         "(exact/segmented1/segmented2/segmented3)")
    ap.add_argument("--backend", default=None,
                    choices=["auto", "pallas", "interpret", "xla"])
    ap.add_argument("--tune", default=None, metavar="TUNE_JSON",
                    help="measured kernel-tuning artifact to activate "
                         "(kernels/TUNE_<device>.json; generate with "
                         "python -m benchmarks.autotune). Default: the "
                         "REPRO_TUNE_FILE env var if set, else the "
                         "static tuning tables")
    ap.add_argument("--weights", default=None, metavar="CKPT",
                    help="pretrained checkpoint loaded through the compat "
                         "converter registered for --arch (safetensors "
                         "file, sharded *.safetensors.index.json or its "
                         "directory, or a torch pickle; see docs/compat.md)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full arch config (default: reduced)")


def parse_tiers(spec: str):
    """``name:policy,name:policy`` -> TierSpec tuple (priority = listed
    order; policy is a preset name or a policy-JSON path).  The wire
    format of ``python -m repro.session serve-loop --tiers``."""
    from repro.serving import TierSpec

    tiers = []
    for i, part in enumerate(p for p in spec.split(",") if p.strip()):
        name, _, pol = part.partition(":")
        if not name.strip() or not pol.strip():
            raise SessionError(f"bad tier spec {part.strip()!r}: expected "
                               f"name:policy (e.g. premium:exact)")
        if any(t.name == name.strip() for t in tiers):
            raise SessionError(f"duplicate tier {name.strip()!r} in --tiers")
        tiers.append(TierSpec(name.strip(), pol.strip(), priority=i))
    if not tiers:
        raise SessionError(f"empty tier spec {spec!r}: expected "
                           f"name:policy[,name:policy...]")
    return tuple(tiers)


def print_ppa_report(ppa: dict, tag: str = "session") -> None:
    """One-line human summary of a ``Session.ppa_report`` dict (shared by
    the session and serve CLIs so the two never drift)."""
    print(f"[{tag}] policy over {ppa['n_sites']} call sites: "
          f"area {ppa['area_um2']:,.0f} um^2 "
          f"(-{ppa['area_reduction']:.1%} vs exact), "
          f"power {ppa['power_w']:.3f} W "
          f"(-{ppa['power_reduction']:.1%}), "
          f"modeled compute latency x{ppa['compute_scale']:.2f}")


def build_parser() -> argparse.ArgumentParser:
    """The unified-CLI argument parser (also what ``tools/gen_cli_docs.py``
    introspects to generate ``docs/cli.md`` — keep help strings current)."""
    ap = argparse.ArgumentParser(
        prog="repro.session",
        description="Unified Session CLI: generate / auto-configure / "
                    "ppa / dryrun over one (arch, policy, backend) spec")
    sub = ap.add_subparsers(dest="cmd", required=True)

    g = sub.add_parser("generate", help="batched prefill + greedy decode")
    _add_common(g)
    g.add_argument("--batch", type=int, default=4)
    g.add_argument("--prompt-len", type=int, default=32)
    g.add_argument("--gen-len", type=int, default=16)
    g.add_argument("--eos-id", type=int, default=None,
                   help="stop token: rows retire when they emit it "
                        "(bit-transparent early exit; default: none)")

    sl = sub.add_parser(
        "serve-loop",
        help="continuous-batching serving demo: a synthetic mixed-tier "
             "workload decodes on one resident weight set (per-tier "
             "accuracy policies; see docs/serving.md)")
    _add_common(sl)
    sl.add_argument("--tiers", default="premium:exact,bulk:segmented1",
                    help="comma list of name:policy tiers, priority in "
                         "listed order (policy: preset name or policy-JSON "
                         "path; overrides --policy per lane)")
    sl.add_argument("--requests", type=int, default=8,
                    help="synthetic workload size (round-robin over tiers)")
    sl.add_argument("--slots", type=int, default=4,
                    help="KV-pool slots per tier")
    sl.add_argument("--max-len", type=int, default=64,
                    help="per-request KV position cap")
    sl.add_argument("--page-size", type=int, default=None,
                    help="tokens per paged-KV page (default 16); requests "
                         "reserve only the pages their own length needs")
    sl.add_argument("--pages", type=int, default=None,
                    help="physical KV pages per tier (default: "
                         "slots * ceil(max_len / page_size))")
    sl.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens prefilled per engine step "
                         "(default 32); long prompts interleave with "
                         "in-flight decodes")
    sl.add_argument("--prompt-len", type=int, default=16)
    sl.add_argument("--gen-len", type=int, default=16)
    sl.add_argument("--aging", type=float, default=None,
                    help="scheduler aging bound in seconds (starvation "
                         "freedom; default: off)")

    a = sub.add_parser("auto-configure",
                       help="budget-driven per-layer numerics sweep "
                            "(proxy: ONE gain-aware calibration pass)")
    _add_common(a)
    a.add_argument("--budget", type=float, required=True)
    a.add_argument("--method", choices=["proxy", "greedy"], default="proxy")
    a.add_argument("--candidates", choices=["segmented", "emulated"],
                   default="segmented")
    a.add_argument("--out", default=None, help="write the policy JSON here")

    p = sub.add_parser("ppa", help="Table II PPA roll-up of the policy")
    _add_common(p)

    d = sub.add_parser(
        "dryrun",
        help="lower+compile one cell (fresh process with XLA_FLAGS="
             "--xla_force_host_platform_device_count=512, or use "
             "python -m repro.launch.dryrun which sets it itself)")
    _add_common(d)
    d.add_argument("--shape", required=True)
    d.add_argument("--multi-pod", action="store_true")
    d.add_argument("--reduced", action="store_true",
                   help="lower the reduced CPU-sized config instead of the "
                        "full arch (dryrun defaults to full-size so records "
                        "match python -m repro.launch.dryrun)")
    return ap


def main(argv=None) -> int:
    from repro.launch.compile_cache import enable_compile_cache

    args = build_parser().parse_args(argv)
    enable_compile_cache()
    # dryrun lowers the full-size arch by default — its records must be
    # comparable with the launch.dryrun CLI; every other subcommand works
    # on the reduced config unless --full-size
    reduced = args.reduced if args.cmd == "dryrun" else not args.full_size
    try:
        if getattr(args, "weights", None):
            from repro.compat import CompatError

            try:
                sess = Session.from_pretrained(
                    args.arch, args.weights, policy=args.policy,
                    backend=args.backend, seed=args.seed, reduced=reduced,
                    tune=args.tune)
            except CompatError as e:
                raise SessionError(str(e)) from e
        else:
            sess = Session(args.arch, policy=args.policy,
                           backend=args.backend, seed=args.seed,
                           reduced=reduced, tune=args.tune)
        if args.cmd == "generate":
            if sess.is_policy:
                print_ppa_report(sess.ppa_report())
            res = sess.generate(batch=args.batch, prompt_len=args.prompt_len,
                                gen_len=args.gen_len, eos_id=args.eos_id)
            n_tok = (int(res.gen_lengths.sum()) if res.gen_lengths is not None
                     else res.tokens.size)
            print(f"[session] {args.arch}: {res.tokens.shape[0]}x"
                  f"{res.tokens.shape[1]} tokens ({n_tok} emitted) in "
                  f"{res.seconds:.2f}s ({res.tokens_per_s:.1f} tok/s)")
        elif args.cmd == "serve-loop":
            from repro.serving import ServingError

            tiers = parse_tiers(args.tiers)
            try:
                eng = sess.serving_engine(tiers, slots=args.slots,
                                          max_len=args.max_len,
                                          page_size=args.page_size,
                                          pages=args.pages,
                                          prefill_chunk=args.prefill_chunk,
                                          aging=args.aging)
                rng = np.random.default_rng(args.seed)
                for i in range(args.requests):
                    spec = tiers[i % len(tiers)]
                    plen = int(rng.integers(max(2, args.prompt_len // 2),
                                            args.prompt_len + 1))
                    eng.submit(rng.integers(0, sess.config.vocab, plen),
                               tier=spec.name,
                               max_new_tokens=args.gen_len)
                t0 = time.perf_counter()
                stats = eng.run()
                dt = time.perf_counter() - t0
            except ServingError as e:
                raise SessionError(str(e)) from e
            total = sum(s.n_tokens for s in stats.values())
            print(f"[serve-loop] {args.arch}: {args.requests} requests, "
                  f"{total} tokens in {dt:.2f}s ({total / dt:.1f} tok/s "
                  f"aggregate)")
            for spec in tiers:
                s = stats[spec.name]
                print(f"[serve-loop]   {spec.name} ({spec.policy}): "
                      f"{s.n_finished} finished, {s.n_tokens} tokens, "
                      f"{s.n_decode_steps} decode steps, mean batch "
                      f"{s.mean_occupancy:.2f}")
                print_ppa_report(sess.replace(policy=spec.policy).ppa_report(),
                                 tag=f"tier:{spec.name}")
        elif args.cmd == "auto-configure":
            res = sess.auto_configure(args.budget, method=args.method,
                                      candidates=args.candidates, verbose=True)
            print(f"[session] {res.method} error={res.error:.3e} "
                  f"(budget {args.budget:g})  area {res.area_um2:,.0f} um^2 "
                  f"(-{res.area_reduction:.1%} vs exact)  "
                  f"[{res.n_evals} calibration evals]")
            if args.out:
                sess.save_policy(args.out)
                print(f"[session] policy written to {args.out}")
        elif args.cmd == "ppa":
            print_ppa_report(sess.ppa_report())
        elif args.cmd == "dryrun":
            rec = sess.dryrun(args.shape, multi_pod=args.multi_pod)
            print(json.dumps(rec, indent=1))
            return 0 if rec.get("status", "error").startswith(
                ("ok", "skipped")) else 1
    except SessionError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
