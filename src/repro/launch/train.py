"""Training launcher.

Two modes:
* ``--arch <id> --smoke``: CPU-runnable reduced-config training (the
  per-arch smoke path; also what examples/train_weak_fm.py drives).
* ``--arch <id>``: full-config training under the production mesh — on
  this CPU container use ``--dry-run`` (via repro.launch.dryrun) to verify
  the distributed step; on a real v5e slice this entry point runs it.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 100 --batch 16 --seq 64
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.cache import enable_compile_cache
from repro.models import init_params
from repro.training import AdamWConfig, init_opt_state, make_train_step
from repro.training.checkpoint import save_checkpoint


def synthetic_lm_batch(rng: np.random.Generator, vocab: int, batch: int,
                       seq: int, cfg) -> dict:
    """Structured synthetic LM data (Zipf-ish marginals + copy structure so
    the loss actually falls during smoke training)."""
    base = rng.zipf(1.5, size=(batch, seq)).astype(np.int64)
    tokens = np.minimum(base, vocab - 1).astype(np.int32)
    # periodic copy structure: second half repeats the first half
    tokens[:, seq // 2:] = tokens[:, :seq - seq // 2]
    labels = np.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    out = {"tokens": tokens, "labels": labels.astype(np.int32)}
    if cfg.family == "vlm":
        out["patch_embeds"] = rng.normal(
            size=(batch, cfg.num_patches, cfg.d_model)).astype(np.float32)
    if cfg.family == "audio":
        out["frames"] = rng.normal(
            size=(batch, cfg.encoder_frames, cfg.d_model)).astype(np.float32)
    return out


def train(arch: str, *, smoke: bool, steps: int, batch: int, seq: int,
          lr: float, ckpt: str | None, log_every: int = 10) -> dict:
    cfg = configs.get_smoke(arch) if smoke else configs.get(arch)
    print(f"[train] {cfg.name}: {cfg.param_count():,} params "
          f"({cfg.active_param_count():,} active)")
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    opt_cfg = AdamWConfig(learning_rate=lr, warmup_steps=min(20, steps // 5),
                          total_steps=steps)
    opt_state = init_opt_state(params)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    metrics = {}
    for i in range(steps):
        b = synthetic_lm_batch(rng, cfg.vocab_size, batch, seq, cfg)
        b = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt_state, metrics = step_fn(params, opt_state, b)
        if (i + 1) % log_every == 0 or i == 0:
            print(f"  step {i + 1}/{steps} loss={float(metrics['loss']):.4f}"
                  f" acc={float(metrics['accuracy']):.3f}"
                  f" lr={float(metrics['lr']):.2e}"
                  f" ({(time.perf_counter() - t0) / (i + 1) * 1e3:.0f} ms/step)")
    if ckpt:
        save_checkpoint(ckpt, {"params": params, "cfg_name": cfg.name})
        print(f"[train] checkpoint → {ckpt}")
    return {k: float(v) for k, v in metrics.items()}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    train(args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
          seq=args.seq, lr=args.lr, ckpt=args.ckpt)


if __name__ == "__main__":
    main()
