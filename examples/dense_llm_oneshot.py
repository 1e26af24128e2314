"""DENSE at LM scale (reduced configs): one-shot federation of *decoder
language models* with heterogeneous architectures (llama-style + qwen-style
+ phi-style), distilled into a global student — the LLM instantiation of
the paper described in DESIGN.md §3/§7.

Clients train on disjoint shards of a Markov token stream (non-IID via
different transition tables), upload once, then the server runs the two
DENSE stages with a token-sequence generator emitting soft embeddings.

  PYTHONPATH=src python examples/dense_llm_oneshot.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_smoke_config
from repro.core import dense_llm as DL
from repro.core.generator import tok_generator_init
from repro.data import lm_batches, make_lm_data
from repro.fl.protocol import param_bytes
from repro.launch import steps as ST
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T

VOCAB = 256
SEQ = 32


def train_client(arch: str, seed: int, steps: int = 40):
    cfg = get_smoke_config(arch).replace(vocab_size=VOCAB)
    state = ST.make_train_state(jax.random.PRNGKey(seed), cfg, lr=3e-3)
    step = jax.jit(ST.make_train_step(cfg, None, lr=3e-3))
    toks = make_lm_data(seed, vocab=VOCAB, n_tokens=40_000)  # disjoint dialect
    for x, y in lm_batches(toks, 8, SEQ, seed=seed, steps=steps):
        state, m = step(state, {"tokens": jnp.asarray(x),
                                "labels": jnp.asarray(y)})
    return cfg, state["params"], float(m["loss"])


def main():
    enable_compile_cache()
    archs = ["llama3.2-3b", "qwen1.5-4b", "musicgen-large"]
    cfgs, params, up = [], [], 0
    for i, a in enumerate(archs):
        cfg, p, loss = train_client(a, seed=i)
        cfgs.append(cfg)
        params.append(p)
        up += param_bytes(p)
        print(f"client[{a}] local LM loss {loss:.3f}")
    print(f"one-shot upload: {up/1e6:.1f} MB, 1 round")

    stu_cfg = get_smoke_config("phi3-medium-14b").replace(vocab_size=VOCAB)
    key = jax.random.PRNGKey(99)
    stu_p = T.init_model(key, stu_cfg)
    gen_p = tok_generator_init(key, nz=16, seq=SEQ, d_model=stu_cfg.d_model,
                               d_g=64, n_classes=VOCAB)
    gstep, sstep, g_opt, s_opt = DL.make_llm_dense_steps(
        stu_cfg, cfgs, gen_seq=SEQ, nz=16, s_lr=3e-4)
    gs, ss = g_opt.init(gen_p), s_opt.init(stu_p)

    for epoch in range(12):
        key, kz, ky = jax.random.split(key, 3)
        z = jax.random.normal(kz, (8, 16))
        y = jax.random.randint(ky, (8, SEQ), 0, VOCAB)
        for _ in range(3):
            gen_p, gs, gl, parts = gstep(gen_p, gs, stu_p, params, z, y)
        stu_p, ss, dl = sstep(stu_p, ss, gen_p, params, z, y)
        if (epoch + 1) % 3 == 0:
            print(f"epoch {epoch+1:2d} gen={float(gl):7.3f} "
                  f"(ce={float(parts['ce']):.3f} bn={float(parts['bn']):.3f} "
                  f"div={float(parts['div']):.3f}) distill_kl={float(dl):.4f}")
    print("done: global student distilled from a heterogeneous LM ensemble "
          "with one communication round and no data.")


if __name__ == "__main__":
    main()
