"""End-to-end LM training driver (example application of the substrate).

Trains a reduced-config model on the procedural Markov LM stream on
whatever devices exist (CPU smoke / real TPU slice via the production
mesh). For the ~100M-scale end-to-end run see examples/train_lm_100m.py.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
      --steps 50 --batch 8 --seq 256 [--smoke] [--model-parallel 1]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs.base import get_config, get_smoke_config
from repro.data import lm_batches, make_lm_data
from repro.launch.mesh import make_host_mesh, dp_axes_of
from repro.launch import shardings as SH
from repro.launch import steps as ST


def train(arch: str, *, steps: int, batch: int, seq: int, smoke: bool,
          lr: float = 3e-4, model_parallel: int = 1, seed: int = 0,
          ckpt: str | None = None, log_every: int = 10):
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if cfg.family == "vlm":
        vision = np.zeros((batch, cfg.n_patches, cfg.vision_dim), np.float32)
    else:
        vision = None

    mesh = make_host_mesh(model_parallel)
    key = jax.random.PRNGKey(seed)
    state = ST.make_train_state(key, cfg, lr=lr)
    step_fn = jax.jit(ST.make_train_step(cfg, mesh, lr=lr),
                      donate_argnums=(0,))

    toks = make_lm_data(seed, vocab=cfg.vocab_size,
                        n_tokens=max(200_000, batch * (seq + 1) * 4))
    t0 = time.time()
    losses = []
    with mesh:
        for i, (x, y) in enumerate(lm_batches(toks, batch, seq, seed=seed,
                                              steps=steps)):
            b = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
            if vision is not None:
                b["vision"] = jnp.asarray(vision)
            state, m = step_fn(state, b)
            losses.append(float(m["loss"]))
            if (i + 1) % log_every == 0:
                dt = time.time() - t0
                print(f"step {i+1:5d} loss {losses[-1]:.4f} "
                      f"ce {float(m['ce']):.4f} "
                      f"({dt/ (i+1):.2f}s/step)", flush=True)
    if ckpt:
        save_checkpoint(ckpt, state["params"],
                        meta={"arch": arch, "steps": steps,
                              "final_loss": losses[-1]})
    return state, losses


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    a = ap.parse_args()
    _, losses = train(a.arch, steps=a.steps, batch=a.batch, seq=a.seq,
                      smoke=a.smoke, lr=a.lr,
                      model_parallel=a.model_parallel, ckpt=a.ckpt)
    print(f"first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
