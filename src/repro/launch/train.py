"""Training driver: data pipeline -> sharded train_step -> checkpoint/restart.

Runs at any scale: on this container it trains reduced configs on the CPU
device; on a pod the same code path runs under the production mesh (the
mesh/rules arguments are the only difference — see launch/dryrun.py for
the production shardings).

Fault tolerance: resumes from the newest committed checkpoint, saves every
``ckpt_every`` steps, records per-step wall time into the straggler
watchdog, and (optionally) compresses cross-pod gradients.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import io as ckpt_io
from repro.configs import get_arch
from repro.data.synthetic import DataConfig, lm_batch
from repro.distributed.fault import StepWatchdog
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer
from repro.models.layers import unzip


def train(arch: str, steps: int = 50, seq_len: int = 128, batch: int = 8,
          ckpt_dir: str | None = None, ckpt_every: int = 20, lr: float = 3e-4,
          reduced: bool = True, log_every: int = 10, seed: int = 0):
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    pp = transformer.init(cfg, jax.random.PRNGKey(seed))
    params, _ = unzip(pp)
    opt_cfg, opt_init, opt_apply, _ = steps_mod.make_optimizer(
        cfg, lr=lr, total_steps=steps, warmup_steps=max(2, steps // 10))
    opt_state = opt_init(params, opt_cfg)

    start_step = 0
    if ckpt_dir:
        latest = ckpt_io.latest_step(ckpt_dir)
        if latest is not None:
            (params, opt_state), manifest = ckpt_io.restore(
                ckpt_dir, (params, opt_state))
            start_step = manifest["step"]
            print(f"[train] restored step {start_step} from {ckpt_dir}")

    train_step = jax.jit(steps_mod.make_train_step(cfg, opt_cfg, opt_apply),
                         donate_argnums=(0, 1))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq_len, global_batch=batch,
                      seed=seed)
    watchdog = StepWatchdog()
    losses = []
    for step in range(start_step, steps):
        hb = lm_batch(dcfg, step)
        b = {k: jnp.asarray(v) for k, v in hb.items()}
        t0 = time.perf_counter()
        params, opt_state, metrics = train_step(params, opt_state, b)
        jax.block_until_ready(metrics["loss"])
        watchdog.record(jax.process_index(), time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] {arch} step {step:5d} loss {losses[-1]:.4f} "
                  f"lr {float(metrics['lr']):.2e} gnorm {float(metrics['grad_norm']):.2f}")
        if ckpt_dir and ((step + 1) % ckpt_every == 0 or step == steps - 1):
            ckpt_io.save(ckpt_dir, step + 1, (params, opt_state),
                         extra={"loss": losses[-1]})
    return params, opt_state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--full-config", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    train(args.arch, steps=args.steps, seq_len=args.seq_len, batch=args.batch,
          ckpt_dir=args.ckpt_dir, reduced=not args.full_config)


if __name__ == "__main__":
    main()
