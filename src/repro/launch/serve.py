"""Serving driver: thin batch-style wrapper + CLI over ServeEngine.

The engine (launch/engine.py) owns the real API — ``submit``/``step``/
``poll``/``drain`` over a paged block-pool cache with continuous
batching (DESIGN.md §12). This module keeps the historical fixed-batch
entry point as a compat wrapper: ``serve(arch, batch=..., ...)`` submits
``batch`` identical-length synthetic prompts and drains, returning the
same ``(tokens, stats)`` pair as before.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --smoke \
      --batch 4 --prompt-len 64 --gen 32 [--mode paged|dense]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import get_config, get_smoke_config
from repro.launch.engine import ServeEngine, engine_keys
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T


def serve(arch: str, *, batch: int, prompt_len: int, gen: int,
          smoke: bool = True, model_parallel: int = 1, seed: int = 0,
          params=None, greedy: bool = True, temperature: float = 1.0,
          mode: str | None = None):
    """Compat wrapper: ``batch`` synthetic requests through a
    ServeEngine. Returns (tokens (batch, gen) int32, stats with
    prefill_s / decode_s / tok_per_s — the historical keys)."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    mesh = make_host_mesh(model_parallel)
    # one split up front: init / prompts / sampling never share a key
    # (the engine derives the sampling stream from the same seed)
    k_init, k_prompt, _ = engine_keys(seed)
    if params is None:
        params = T.init_model(k_init, cfg)
    prompts = np.asarray(jax.random.randint(
        k_prompt, (batch, prompt_len), 0, cfg.vocab_size), np.int32)

    eng = ServeEngine(cfg, params, mesh=mesh, max_reqs=batch,
                      max_len=prompt_len + gen, mode=mode, seed=seed)
    sampling = None if greedy else {"temperature": temperature}
    rids = [eng.submit(prompts[i], max_new=gen, sampling=sampling)
            for i in range(batch)]
    results = eng.drain()
    tokens = np.stack([results[r] for r in rids])
    decode_s = eng.stats["decode_s"]
    return tokens, {"prefill_s": eng.stats["prefill_s"],
                    "decode_s": decode_s,
                    "tok_per_s": batch * gen / max(decode_s, 1e-9)}


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--mode", choices=["paged", "dense"], default=None,
                    help="engine mode (default: paged where supported)")
    a = ap.parse_args()
    toks, stats = serve(a.arch, batch=a.batch, prompt_len=a.prompt_len,
                        gen=a.gen, smoke=a.smoke,
                        model_parallel=a.model_parallel, mode=a.mode)
    print("generated shape:", toks.shape)
    print({k: round(v, 3) for k, v in stats.items()})


if __name__ == "__main__":
    main()
