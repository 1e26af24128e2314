"""Compile-only rehearsal of the Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see the TPU's tiling
rule or its kernel compiler, so these tests compile each kernel program
for a *described* v5e chip — the TPU compiler is installed, no chip is
needed — at published widths with the registry's TPU block shapes
(``configs.backend._BLOCKS["tpu"]``), and check that the compiled
program holds the Pallas kernel (``tpu_custom_call``). Nothing runs.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and a decision made
at collection would give parallel test workers different test lists.
The persistent compilation cache is off around these compiles, since an
entry compiled for a described chip cannot be read back without one.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.backend import resolve_exec_policy
from repro.configs.llama3_2_3b import CONFIG as LLAMA
from repro.configs.mamba2_130m import CONFIG as MAMBA


def _kernel(name):
    # repro.kernels re-exports the ops wrappers under the submodule names
    return importlib.import_module(f"repro.kernels.{name}")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def tpu_policy():
    return resolve_exec_policy(None, backend="tpu")


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; return the compiled text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize("rows,vocab", [
    (128, 10),             # the paper's round: synth batch 128, 10 classes
    (4096, LLAMA.vocab_size),    # LLM distill step, 128256-way vocab
    (4096, MAMBA.vocab_size),    # ragged vocab tail (50280 % 1024 != 0)
], ids=["paper-128x10", "llama-4096x128256", "mamba-4096x50280"])
def test_distill_kl_compiles(one_chip, tpu_policy, rows, vocab, direction):
    kl = _kernel("distill_kl")
    br, bv = tpu_policy.blocks_for("distill_kl")
    bbr, bbv = tpu_policy.blocks_for("distill_kl_bwd")

    def loss(t, s):
        return kl.distill_kl_vjp(t, s, br, bv, False, False, bbr, bbv).sum()

    fn = loss if direction == "fwd" else jax.grad(loss, argnums=1)
    x = _shape(one_chip, (rows, vocab))
    assert "tpu_custom_call" in _compile(fn, x, x)


@pytest.mark.parametrize("direction", ["fwd", "grad"])
def test_flash_attention_compiles(one_chip, tpu_policy, direction):
    fa = _kernel("flash_attention")
    bq, bk = tpu_policy.blocks_for("flash_attention")
    gq, gk = tpu_policy.blocks_for("flash_attention_bwd")
    s, hd = 2048, LLAMA.head_dim

    def loss(q, k, v):
        out = fa.flash_attention_vjp(q, k, v, True, 0, None, bq, bk, False,
                                     gq, gk)
        return out.astype(jnp.float32).sum()

    fn = loss if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    q = _shape(one_chip, (1, LLAMA.n_heads, s, hd), jnp.bfloat16)
    kv = _shape(one_chip, (1, LLAMA.n_kv_heads, s, hd), jnp.bfloat16)
    assert "tpu_custom_call" in _compile(fn, q, kv, kv)


@pytest.mark.parametrize("direction", ["fwd", "grad"])
def test_ssd_scan_compiles(one_chip, tpu_policy, direction):
    ssd = _kernel("ssd_scan")
    (chunk,) = tpu_policy.blocks_for("ssd_scan")
    s, h = 2048, MAMBA.n_ssm_heads
    p, n, g = MAMBA.ssm_head_dim, MAMBA.ssm_state, MAMBA.ssm_n_groups

    def loss(x, dt, a, b, c, h0):
        y, final = ssd.ssd_scan_vjp(x, dt, a, b, c, h0, chunk, False)
        return y.sum() + final.sum()

    fn = loss if direction == "fwd" else jax.grad(loss, argnums=range(6))
    args = (_shape(one_chip, (1, s, h, p)), _shape(one_chip, (1, s, h)),
            _shape(one_chip, (h,)), _shape(one_chip, (1, s, g, n)),
            _shape(one_chip, (1, s, g, n)), _shape(one_chip, (1, h, p, n)))
    assert "tpu_custom_call" in _compile(fn, *args)


def test_paged_attention_compiles(one_chip, tpu_policy):
    pa = _kernel("paged_attention")
    (page,) = tpu_policy.blocks_for("paged_attention")
    reqs, slots = 8, 16                       # 8 requests of up to 2048
    pool = _shape(one_chip, (1 + reqs * slots, LLAMA.n_kv_heads, page,
                             LLAMA.head_dim), jnp.bfloat16)
    text = _compile(pa.paged_attention,
                    _shape(one_chip, (reqs, LLAMA.n_heads, LLAMA.head_dim),
                           jnp.bfloat16),
                    pool, pool, _shape(one_chip, (reqs, slots), jnp.int32),
                    _shape(one_chip, (reqs,), jnp.int32))
    assert "tpu_custom_call" in text
