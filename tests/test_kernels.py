"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py).

Kernels run in interpret mode on CPU. That checks the numerics only:
interpret mode never applies the TPU's block-tiling rule or runs its
kernel compiler, so it passes kernels the chip would refuse. The TPU
lowering is exercised by tests/test_tpu_compile.py, which compiles each
kernel for a described v5e.

The custom-VJP suites (distill_kl, flash_attention, ssd_scan — the §9
kernel pairs) double as CI's ``kernel-grads`` matrix: ``KERNEL_GRAD_DTYPE``
/ ``KERNEL_GRAD_BLOCKS`` (e.g. ``bfloat16`` / ``4x96``) restrict the
parametrization to one matrix cell so each CI job runs a focused slice;
unset (local runs) the full sweep executes. The block-name axis maps to
per-kernel block geometries (`_ATTN_GRAD_BLOCKS` / `_SSD_GRAD_CHUNKS`) so
one matrix covers all three pairs."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import backend as B
from repro.kernels import ops, ref
from _hyp import given, settings, st

KEY = jax.random.PRNGKey(0)

# every ops.* call here pins its geometry through an explicit ExecPolicy
# (the legacy block/interpret/vjp_mode kwargs are on the PR 11 removal
# schedule — kernels/ops.py; the shim itself is pinned by
# tests/test_backend.py's shim-equivalence suite until then)
_POL = B.resolve_exec_policy(None)


def _attn_pol(bq, bk, mode="autodiff"):
    return _POL.override_blocks("flash_attention", block_q=bq,
                                block_k=bk).replace(kernel_vjp=mode)


def _ssd_pol(chunk, mode="autodiff"):
    return _POL.override_blocks("ssd_scan",
                                chunk=chunk).replace(kernel_vjp=mode)


def _kl_pol(br, bv):
    return _POL.override_blocks("distill_kl", block_rows=br, block_v=bv)


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Sk,D,win,dtype", [
    (2, 4, 2, 64, 64, 32, 0, jnp.float32),
    (1, 4, 4, 128, 128, 16, 0, jnp.float32),
    (2, 8, 2, 64, 64, 32, 24, jnp.float32),
    (1, 2, 1, 32, 128, 64, 0, jnp.float32),     # cross Sq != Sk (decode tail)
    (1, 4, 2, 64, 64, 32, 0, jnp.bfloat16),
    (1, 2, 2, 64, 64, 128, 16, jnp.float32),
])
def test_flash_attention_vs_ref(B, Hq, Hkv, Sq, Sk, D, win, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, D), dtype)
    out = ops.flash_attention(q, k, v, window=win, policy=_attn_pol(32, 32))
    want = ref.attention(q, k, v, window=win)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("R,V,br,bv,dtype", [
    (8, 512, 4, 128, jnp.float32),
    (16, 4096, 8, 1024, jnp.float32),
    (4, 1000, 4, 500, jnp.float32),
    (8, 512, 8, 512, jnp.bfloat16),
    # ragged: V % bv != 0 and/or R % br != 0 (tail blocks masked in-kernel)
    (8, 384, 8, 100, jnp.float32),
    (10, 250, 4, 128, jnp.float32),
    (7, 300, 4, 96, jnp.float32),
])
def test_distill_kl_vs_ref(R, V, br, bv, dtype):
    ks = jax.random.split(KEY, 2)
    t = (jax.random.normal(ks[0], (R, V)) * 3).astype(dtype)
    s = (jax.random.normal(ks[1], (R, V)) * 3).astype(dtype)
    out = ops.distill_kl(t, s, policy=_kl_pol(br, bv))
    want = ref.distill_kl(t, s)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=tol)


# ---------------------------------------------- distill_kl custom VJP --
#
# The fused backward kernel (kernels/distill_kl.distill_kl_bwd) vs
# jax.grad of the materialized reference. CI's kernel-grads job runs one
# (dtype x block-shape) cell per matrix entry via the env vars below.

_GRAD_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_GRAD_BLOCKS = {"8x128": (8, 128), "4x96": (4, 96)}


def _grad_matrix():
    dt = os.environ.get("KERNEL_GRAD_DTYPE")
    bl = os.environ.get("KERNEL_GRAD_BLOCKS")
    dtypes = [dt] if dt else list(_GRAD_DTYPES)
    blocks = [bl] if bl else list(_GRAD_BLOCKS)
    return [(d, b) for d in dtypes for b in blocks]


def _vjp_pair(t, s, br, bv, g, **kw):
    _, pull = jax.vjp(
        lambda a, b: ops.distill_kl(a, b, policy=_kl_pol(br, bv), **kw),
        t, s)
    return pull(g)


@pytest.mark.parametrize("dtype_name,block_name", _grad_matrix())
@pytest.mark.parametrize("R,V", [(16, 512), (10, 384), (7, 250)])
def test_distill_kl_vjp_matches_ref_grads(dtype_name, block_name, R, V):
    dtype = _GRAD_DTYPES[dtype_name]
    br, bv = _GRAD_BLOCKS[block_name]
    ks = jax.random.split(KEY, 3)
    t = (jax.random.normal(ks[0], (R, V)) * 3).astype(dtype)
    s = (jax.random.normal(ks[1], (R, V)) * 3).astype(dtype)
    g = jax.random.normal(ks[2], (R,))          # non-uniform cotangent
    dt, ds = _vjp_pair(t, s, br, bv, g)
    dt_r, ds_r = ref.distill_kl_grads(t, s, g)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(dt, np.float32),
                               np.asarray(dt_r, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(ds, np.float32),
                               np.asarray(ds_r, np.float32), atol=tol)


def test_distill_kl_vjp_neg_inf_padding_columns():
    """NEG_INF-padded vocab columns (ragged-vocab convention): zero KL
    contribution and exactly-zero gradients on the padded lanes."""
    from repro.kernels.distill_kl import NEG_INF
    R, V, real = 8, 320, 300
    ks = jax.random.split(KEY, 3)
    t = jax.random.normal(ks[0], (R, V)) * 3
    s = jax.random.normal(ks[1], (R, V)) * 3
    t = t.at[:, real:].set(NEG_INF)
    s = s.at[:, real:].set(NEG_INF)
    out = ops.distill_kl(t, s, policy=_kl_pol(4, 128))
    want = ref.distill_kl(t[:, :real], s[:, :real])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    g = jax.random.normal(ks[2], (R,))
    dt, ds = _vjp_pair(t, s, 4, 128, g)
    dt_r, ds_r = ref.distill_kl_grads(t, s, g)
    np.testing.assert_allclose(np.asarray(dt), np.asarray(dt_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_r), atol=1e-5)
    assert float(jnp.max(jnp.abs(dt[:, real:]))) == 0.0
    assert float(jnp.max(jnp.abs(ds[:, real:]))) == 0.0


def test_distill_kl_vjp_extreme_logits():
    """±1e4 logits: the online-LSE stats and the streamed backward must
    stay finite and track the reference (f32 rounding at this scale is
    ~1e-3 absolute, identical for both formulations)."""
    ks = jax.random.split(KEY, 3)
    R, V = 8, 256
    t = jax.random.choice(ks[0], jnp.array([-1e4, 0.0, 1e4]), (R, V)) \
        + jax.random.normal(ks[1], (R, V))
    s = jnp.roll(t, 7, axis=1) + jax.random.normal(ks[2], (R, V))
    out = ops.distill_kl(t, s, policy=_kl_pol(4, 64))
    want = ref.distill_kl(t, s)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-2)
    g = jnp.ones((R,)) / R
    dt, ds = _vjp_pair(t, s, 4, 64, g)
    dt_r, ds_r = ref.distill_kl_grads(t, s, g)
    assert bool(jnp.all(jnp.isfinite(dt))) and bool(jnp.all(jnp.isfinite(ds)))
    # dt entries are p * ((t - lse_t) - (s - lse_s) - KL): differences of
    # 1e4-scale f32 terms, so ~1e-3 relative agreement is the f32 floor
    np.testing.assert_allclose(np.asarray(dt), np.asarray(dt_r),
                               rtol=1e-3, atol=1e-2)
    np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_r),
                               rtol=1e-3, atol=1e-2)


def test_distill_kl_vjp_without_teacher_grad():
    """with_teacher_grad=False: identical dL/ds, zeros dL/dt (the stream
    is skipped for stop-gradient'd teachers)."""
    ks = jax.random.split(KEY, 2)
    t = jax.random.normal(ks[0], (6, 130))
    s = jax.random.normal(ks[1], (6, 130))
    g = jnp.ones((6,))
    dt, ds = _vjp_pair(t, s, 4, 64, g, with_teacher_grad=False)
    _, ds_full = _vjp_pair(t, s, 4, 64, g)
    assert float(jnp.max(jnp.abs(dt))) == 0.0
    np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_full), atol=0)


def test_distill_kl_forward_persists_stats():
    """return_stats=True: the persisted accumulators reconstruct the
    row log-sum-exps and the KL identity KL = S/Z_t - lse_t + lse_s."""
    from repro.kernels.distill_kl import distill_kl
    ks = jax.random.split(KEY, 2)
    t = jax.random.normal(ks[0], (8, 300)) * 3
    s = jax.random.normal(ks[1], (8, 300)) * 3
    kl, (mt, zt, st, ms, zs) = distill_kl(t, s, block_rows=4, block_v=128,
                                          interpret=True, return_stats=True)
    lse_t = mt + jnp.log(zt)
    lse_s = ms + jnp.log(zs)
    np.testing.assert_allclose(np.asarray(lse_t),
                               np.asarray(jax.nn.logsumexp(t, axis=-1)),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_s),
                               np.asarray(jax.nn.logsumexp(s, axis=-1)),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(st / zt - lse_t + lse_s),
                               np.asarray(kl), atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 12), st.integers(2, 160), st.integers(1, 5),
       st.integers(1, 70), st.integers(0, 2 ** 31 - 1))
def test_distill_kl_vjp_property(R, V, br, bv, seed):
    """Property: for ANY (R, V, block) combination — divisible or not —
    fused forward and VJP match the materialized reference."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    t = jax.random.normal(ks[0], (R, V)) * 4
    s = jax.random.normal(ks[1], (R, V)) * 4
    g = jax.random.normal(ks[2], (R,))
    out = ops.distill_kl(t, s, policy=_kl_pol(br, bv))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.distill_kl(t, s)), atol=2e-5)
    dt, ds = _vjp_pair(t, s, br, bv, g)
    dt_r, ds_r = ref.distill_kl_grads(t, s, g)
    np.testing.assert_allclose(np.asarray(dt), np.asarray(dt_r), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_r), atol=2e-5)


@pytest.mark.parametrize("B,S,H,P,G,N,cl", [
    (2, 64, 4, 16, 1, 32, 16),
    (1, 128, 8, 32, 2, 16, 32),
    (1, 64, 4, 64, 1, 64, 64),
    (2, 96, 6, 16, 3, 8, 32),
])
def test_ssd_scan_vs_sequential_ref(B, S, H, P, G, N, cl):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    c = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y, st = ops.ssd_scan(x, dt, a, b, c, policy=_ssd_pol(cl))
    y2, st2 = ref.ssd(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=2e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st2), atol=2e-3)


def test_ssd_scan_matches_model_chunked_impl():
    """Kernel vs the model-level chunked jnp implementation (third algo)."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(KEY, 5)
    B, S, H, P, G, N = 1, 64, 4, 16, 1, 32
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    c = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y1, s1 = ops.ssd_scan(x, dt, a, b, c, policy=_ssd_pol(16))
    y2, s2 = ssd_chunked(x, dt, a, b, c, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-3)


# ------------------------------------------- flash_attention custom VJP --
#
# The streaming backward kernels (kernels/flash_attention.flash_attention_bwd)
# vs jax.vjp of the materialized reference — CI's kernel-grads matrix runs
# one (dtype x block) cell per job via the env vars above. The block-name
# axis maps to attention tile shapes here (divisible AND ragged-vs-32
# geometries per cell).

_ATTN_GRAD_BLOCKS = {"8x128": (32, 32), "4x96": (32, 16)}

# (B, Hq, Hkv, Sq, Sk, window): GQA ratios, ragged tails, cross Sq != Sk,
# and a window shorter than the k-block (fully-masked dead blocks)
_ATTN_GRAD_SHAPES = [
    (1, 4, 2, 64, 64, 0),
    (1, 2, 2, 48, 48, 0),        # ragged vs 32-wide blocks
    (1, 4, 1, 40, 72, 16),       # 4:1 GQA + ragged + decode-style cross
    (2, 2, 2, 64, 64, 8),        # window < block: dead k-blocks
]


def _attn_vjp(q, k, v, g, win, bq, bk):
    f = lambda a, b, c: ops.flash_attention(
        a, b, c, window=win, policy=_attn_pol(bq, bk, "fused"))
    out, pull = jax.vjp(f, q, k, v)
    return out, pull(g)


@pytest.mark.parametrize("dtype_name,block_name", _grad_matrix())
@pytest.mark.parametrize("B,Hq,Hkv,Sq,Sk,win", _ATTN_GRAD_SHAPES)
def test_flash_attention_vjp_matches_ref_grads(dtype_name, block_name,
                                               B, Hq, Hkv, Sq, Sk, win):
    dtype = _GRAD_DTYPES[dtype_name]
    bq, bk = _ATTN_GRAD_BLOCKS[block_name]
    D = 16
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, D), dtype)
    g = jax.random.normal(ks[3], (B, Hq, Sq, D), dtype)  # non-uniform cotangent
    out, grads = _attn_vjp(q, k, v, g, win, bq, bk)
    want = ref.attention(q, k, v, window=win)
    grads_r = ref.attention_grads(q, k, v, g, window=win)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    for got, ref_g in zip(grads, grads_r):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref_g, np.float32), atol=tol)


def test_flash_attention_ragged_tails_no_longer_crash():
    """Regression: Sq/Sk not a block multiple used to hit the hard
    ``Sq % bq == 0 and Sk % bk == 0`` assert; now the tail blocks are
    masked in-kernel and match the oracle."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 40, 16))
    k = jax.random.normal(ks[1], (1, 2, 40, 16))
    v = jax.random.normal(ks[2], (1, 2, 40, 16))
    out = ops.flash_attention(q, k, v, policy=_attn_pol(32, 32))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.attention(q, k, v)), atol=1e-5)


def test_flash_attention_fully_masked_kblock_regression():
    """Regression for the dead-block bug: a k-block with every key masked
    used to add exp(NEG_INF - NEG_INF) = 1 per lane into l while
    m == NEG_INF. In the pure forward the inflation washed out of o once
    a live block arrived (alpha = exp(NEG_INF - m_real) underflows to 0),
    but it corrupted the *persisted* (m, l) statistic — the residual the
    streaming backward folds into lse and divides its recomputed p by —
    for any row with NO live key at all. The discriminating probe is
    therefore the stats: lse must be the exact live-mass logsumexp, and
    exactly NEG_INF (zero mass, provably zero backward contribution) for
    never-live rows; the unmasked formulation yields
    NEG_INF + log(n_dead_lanes) there instead."""
    from repro.kernels.flash_attention import NEG_INF, flash_attention
    ks = jax.random.split(KEY, 3)
    # (a) windowed geometry with dead blocks for late rows: forward and
    # stats must match the materialized oracle
    S, win, bk = 96, 8, 32
    q = jax.random.normal(ks[0], (1, 2, S, 16))
    k = jax.random.normal(ks[1], (1, 2, S, 16))
    v = jax.random.normal(ks[2], (1, 2, S, 16))
    out, _, lse = flash_attention(q, k, v, window=win, block_q=32,
                                  block_k=bk, interpret=True,
                                  return_stats=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.attention(q, k, v,
                                                        window=win)),
                               atol=1e-5)
    scores = np.einsum("bhsd,bhtd->bhst", np.asarray(q),
                       np.asarray(k)) / 4.0
    pos = np.arange(S)
    live = (pos[None, :] <= pos[:, None]) & (pos[:, None] - pos[None, :] < win)
    masked = np.where(live[None, None], scores, -np.inf)
    want_lse = np.log(np.sum(np.exp(masked), axis=-1))
    np.testing.assert_allclose(np.asarray(lse).reshape(1, 2, S), want_lse,
                               atol=1e-4)
    # (b) never-live rows (causal with Sq > Sk: q_pos < 0): l must be
    # EXACTLY zero mass -> lse pinned to NEG_INF, output exactly 0
    Sq, Sk = 8, 4
    q2 = jax.random.normal(ks[0], (1, 1, Sq, 16))
    k2 = jax.random.normal(ks[1], (1, 1, Sk, 16))
    v2 = jax.random.normal(ks[2], (1, 1, Sk, 16))
    out2, _, lse2 = flash_attention(q2, k2, v2, block_q=4, block_k=4,
                                    interpret=True, return_stats=True)
    dead = np.asarray(lse2).reshape(Sq)[:Sq - Sk]
    np.testing.assert_array_equal(dead, np.full(Sq - Sk, NEG_INF))
    assert float(jnp.max(jnp.abs(out2[:, :, :Sq - Sk]))) == 0.0


# -------------------------------------------------- ssd_scan custom VJP --
#
# The reversed-recurrence backward kernel (kernels/ssd_scan.ssd_scan_bwd)
# vs jax.vjp of the sequential reference, from per-chunk carried-state
# residuals. Same CI matrix; the block-name axis maps to chunk lengths
# (ragged and divisible cells).

_SSD_GRAD_CHUNKS = {"8x128": 32, "4x96": 16}

# (B, S, H, P, G, N, nonzero initial state)
_SSD_GRAD_SHAPES = [
    (1, 64, 4, 16, 2, 16, False),
    (1, 40, 2, 8, 1, 8, True),    # ragged tail chunk + state handoff
    (2, 48, 4, 16, 4, 8, True),   # G == H (rep 1) + ragged for cl=32
]


def _ssd_inputs(B, S, H, P, G, N, dtype, with_init):
    ks = jax.random.split(KEY, 8)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = (jax.random.normal(ks[3], (B, S, G, N)) * 0.3).astype(dtype)
    c = (jax.random.normal(ks[4], (B, S, G, N)) * 0.3).astype(dtype)
    s0 = (jax.random.normal(ks[5], (B, H, P, N)) * 0.5 if with_init
          else jnp.zeros((B, H, P, N), jnp.float32))
    gy = jax.random.normal(ks[6], (B, S, H, P))
    gs = jax.random.normal(ks[7], (B, H, P, N)) * 0.1
    return x, dt, a, b, c, s0, gy, gs


@pytest.mark.parametrize("dtype_name,block_name", _grad_matrix())
@pytest.mark.parametrize("B,S,H,P,G,N,init", _SSD_GRAD_SHAPES)
def test_ssd_scan_vjp_matches_ref_grads(dtype_name, block_name,
                                        B, S, H, P, G, N, init):
    dtype = _GRAD_DTYPES[dtype_name]
    cl = _SSD_GRAD_CHUNKS[block_name]
    x, dt, a, b, c, s0, gy, gs = _ssd_inputs(B, S, H, P, G, N, dtype, init)
    f = lambda *ar: ops.ssd_scan(*ar, policy=_ssd_pol(cl, "fused"))
    (y, st), pull = jax.vjp(f, x, dt, a, b, c, s0)
    yr, st_r = ref.ssd(x, dt, a, b, c, initial_state=s0)
    # bf16 grads additionally carry the output-cast quantization, hence
    # the relative term (both sides round, but at different points)
    tol, rtol = (1e-4, 0) if dtype == jnp.float32 else (5e-2, 2e-2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=tol,
                               rtol=rtol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_r), atol=tol,
                               rtol=rtol)
    grads = pull((gy.astype(y.dtype), gs))
    grads_r = ref.ssd_grads(x, dt, a, b, c, s0, gy, gs)
    for got, ref_g in zip(grads, grads_r):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref_g, np.float32), atol=tol,
                                   rtol=rtol)


def test_ssd_scan_ragged_tail_no_longer_crashes():
    """Regression: S not a chunk multiple used to hit the hard
    ``S % cl == 0`` assert; the masked tail chunk must contribute zero to
    the carried state (dt = 0 on masked lanes)."""
    x, dt, a, b, c, _, _, _ = _ssd_inputs(1, 40, 2, 8, 1, 8,
                                          jnp.float32, False)
    y, st = ops.ssd_scan(x, dt, a, b, c, policy=_ssd_pol(32))
    yr, st_r = ref.ssd(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_r), atol=2e-3)


def test_ssd_scan_initial_state_regression():
    """Regression for the dropped-state bug: the kernel zeroed its state
    carry unconditionally, so a nonzero initial_state (prefill→decode
    handoff) silently fell back to a cold start while the ref.ssd oracle
    honored it."""
    x, dt, a, b, c, s0, _, _ = _ssd_inputs(1, 64, 2, 8, 1, 8,
                                           jnp.float32, True)
    y, st = ops.ssd_scan(x, dt, a, b, c, s0, policy=_ssd_pol(16))
    yr, st_r = ref.ssd(x, dt, a, b, c, initial_state=s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_r), atol=2e-3)
    # a cold start must now DISAGREE (the old kernel returned this)
    y0, _ = ops.ssd_scan(x, dt, a, b, c, policy=_ssd_pol(16))
    assert float(jnp.max(jnp.abs(y0 - y))) > 1e-3


def test_ssd_scan_prefill_decode_handoff():
    """Split a sequence at a non-chunk boundary and thread the carried
    state: kernel(first) + kernel(rest, initial_state=carried) must equal
    one full-sequence kernel pass."""
    x, dt, a, b, c, _, _, _ = _ssd_inputs(1, 56, 2, 8, 2, 8,
                                          jnp.float32, False)
    cut = 24
    y_full, st_full = ops.ssd_scan(x, dt, a, b, c, policy=_ssd_pol(16))
    y1, st1 = ops.ssd_scan(x[:, :cut], dt[:, :cut], a, b[:, :cut],
                           c[:, :cut], policy=_ssd_pol(16))
    y2, st2 = ops.ssd_scan(x[:, cut:], dt[:, cut:], a, b[:, cut:],
                           c[:, cut:], st1, policy=_ssd_pol(16))
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=2e-3)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               atol=2e-3)


def test_kernel_vjp_mode_ref_and_unknown():
    """"ref" routes to the oracles; unknown modes fail fast — including
    a hand-built policy carrying a bogus kernel_vjp (the wrappers
    re-validate, so a stale ExecPolicy can't silently fall through to
    the forward-kernel branch)."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 32, 16))
    out = ops.flash_attention(q, q, q, policy=_POL.replace(kernel_vjp="ref"))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.attention(q, q, q)), atol=0)
    with pytest.raises(ValueError, match="unknown kernel_vjp mode"):
        ops.flash_attention(q, q, q,
                            policy=_POL.replace(kernel_vjp="pallas"))
    x, dt, a, b, c, _, _, _ = _ssd_inputs(1, 32, 2, 8, 1, 8,
                                          jnp.float32, False)
    with pytest.raises(ValueError, match="unknown kernel_vjp mode"):
        ops.ssd_scan(x, dt, a, b, c, policy=_POL.replace(kernel_vjp="nope"))
