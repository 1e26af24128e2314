"""Attention variants: GQA (w/ sliding window, QKV bias, KV cache,
cross-attention) and MLA (DeepSeek-V2 multi-head latent attention).

All functions are pure; caches are explicit pytrees threaded by the caller.

Mask convention: ``window`` is an int32 (possibly traced, so one scanned
layer body can serve both local and global layers — gemma3's 5:1 pattern).
``window == 0`` means full causal attention; ``window = w`` keeps keys with
``q_pos - k_pos < w``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.configs.backend import arch_policy
from repro.configs.base import ArchConfig

NEG_INF = -2.0 ** 30


# ------------------------------------------------------------------- GQA ---

def gqa_init(key, cfg: ArchConfig, *, dtype) -> dict:
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L.linear_init(ks[0], d, h * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": L.linear_init(ks[1], d, kh * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": L.linear_init(ks[2], d, kh * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": L.linear_init(ks[3], h * hd, d, dtype=dtype),
    }


def gqa_cache_init(cfg: ArchConfig, batch: int, max_len: int, dtype) -> dict:
    kh, hd = cfg.n_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, max_len, kh, hd), dtype),
            "v": jnp.zeros((batch, max_len, kh, hd), dtype)}


def _sdpa(q, k, v, mask, scale):
    """q:(B,S,Kh,G,Dh) k/v:(B,T,Kh,Dh) mask:(B,S,T) or (S,T) -> (B,S,Kh,G,Dh)."""
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v)


# Blockwise (flash-style) online-softmax attention in pure XLA: outer
# lax.map over query chunks, inner lax.scan over KV chunks. Peak activation
# per layer is O(bq*bk) instead of O(Sq*Sk) — at prefill_32k that removes
# the dominant HBM term of the whole framework (EXPERIMENTS.md §Perf-1).
BLOCKWISE_MIN = 4096        # use blockwise when Sq >= this and divisible
BLOCK_Q = 1024
BLOCK_KV = 1024


def _sdpa_blockwise(q, k, v, q_pos, k_pos, window, scale,
                    bq: int | None = None, bk: int | None = None):
    """Same contract as _sdpa but mask given by positions + window.

    q: (B,S,Kh,G,Dh); k: (B,T,Kh,Dk); v: (B,T,Kh,Dv) (Dk may differ from
    Dv — MLA). q_pos: (S,), k_pos: (T,), window: int32 scalar (0 = full).
    """
    bq = BLOCK_Q if bq is None else bq
    bk = BLOCK_KV if bk is None else bk
    B, S, Kh, G, Dk = q.shape
    T, Dv = k.shape[1], v.shape[-1]
    nq, nk = S // bq, T // bk
    w = jnp.asarray(window, jnp.int32)

    kb = jnp.moveaxis(k.reshape(B, nk, bk, Kh, Dk), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, bk, Kh, Dv), 1, 0)
    kpb = k_pos.reshape(nk, bk)

    def q_chunk(args):
        qc, qpc = args                                  # (B,bq,Kh,G,Dk),(bq,)

        def kv_step(carry, inp):
            m, l, acc = carry
            k_b, v_b, kp_b = inp
            s = jnp.einsum("bqkgd,btkd->bkgqt", qc, k_b,
                           preferred_element_type=jnp.float32) * scale
            mask = (kp_b[None, :] <= qpc[:, None]) \
                & ((qpc[:, None] - kp_b[None, :] < w) | (w == 0))
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p.astype(v_b.dtype), v_b,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Kh, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kh, G, bq), jnp.float32)
        a0 = jnp.zeros((B, Kh, G, bq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kb, vb, kpb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1)                  # (B,bq,Kh,G,Dv)

    qb = jnp.moveaxis(q.reshape(B, nq, bq, Kh, G, Dk), 1, 0)
    qpb = q_pos.reshape(nq, bq)
    out = jax.lax.map(q_chunk, (qb, qpb))               # (nq,B,bq,Kh,G,Dv)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, Kh, G, Dv)
    return out.astype(v.dtype)


def _use_blockwise(sq: int, t: int, bq=None, bk=None) -> bool:
    bq = BLOCK_Q if bq is None else bq
    bk = BLOCK_KV if bk is None else bk
    return sq >= BLOCKWISE_MIN and sq % bq == 0 and t % bk == 0


def _static_window(window):
    """``int(window)`` when the window is a compile-time constant, else
    None. The Pallas kernel path bakes the window into the kernel body, so
    a traced window (gemma3's scanned local/global layer pattern) keeps
    the XLA path."""
    try:
        return int(window)
    except (TypeError, jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        return None


def gqa_apply(p: dict, x: jnp.ndarray, cfg: ArchConfig, *,
              positions: jnp.ndarray, window=0,
              cache: dict | None = None, cache_pos=None):
    """Self-attention. x:(B,S,D); positions:(S,) absolute token positions.

    Train/prefill: cache=None or a cache to fill (prefill).
    Decode: S==1, cache holds past K/V, cache_pos = scalar write index.
    Returns (y, new_cache).
    """
    B, S, D = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kh
    q = L.linear(p["wq"], x).reshape(B, S, h, hd)
    k = L.linear(p["wk"], x).reshape(B, S, kh, hd)
    v = L.linear(p["wv"], x).reshape(B, S, kh, hd)

    cos, sin = L.rope_cos_sin(positions, hd, cfg.rope_theta)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)

    sw = _static_window(window)
    pol = arch_policy(cfg)
    if pol.kernel_vjp != "ref" and cache is None and sw is not None:
        # Pallas kernel route (configs.backend.arch_policy, DESIGN.md §9):
        # "fused" differentiates through the streaming custom-VJP pair —
        # the path DENSE stage-2 distillation takes when the student (or
        # the generator's teacher ensemble) is an attention LM. Diverges
        # BEFORE the positions-based mask construction below: the kernel
        # builds causal/window masks from block indices, under the
        # contract that positions are contiguous (every cache=None call
        # site passes arange(S)); traced windows and decode/prefill stay
        # on the XLA paths. Block shapes ride on the policy
        # (cfg.attn_block_q/kv as explicit overrides, else the
        # registry/autotuner choice).
        from repro.kernels import ops as kops
        out = kops.flash_attention(
            jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
            jnp.moveaxis(v, 1, 2), causal=True, window=sw, policy=pol)
        out = jnp.moveaxis(out, 1, 2)                    # (B, S, h, hd)
        return L.linear(p["wo"], out.reshape(B, S, h * hd).astype(x.dtype)), \
            None

    if cache is not None:
        pos = positions[0] if cache_pos is None else cache_pos
        k_all = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                             (0, pos, 0, 0))
        v_all = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                             (0, pos, 0, 0))
        new_cache = {"k": k_all, "v": v_all}
        T = k_all.shape[1]
        k_pos = jnp.arange(T)
        q_pos = positions[:, None]                       # (S,1) absolute
        mask = k_pos[None, :] <= q_pos                   # causal over cache
    else:
        new_cache = None
        k_all, v_all = k, v
        T = S
        k_pos = positions
        q_pos = positions[:, None]
        mask = k_pos[None, :] <= q_pos

    w = jnp.asarray(window, jnp.int32)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    q = q.reshape(B, S, kh, g, hd)
    if cfg.use_blockwise_attn and _use_blockwise(S, T, cfg.attn_block_q,
                                                 cfg.attn_block_kv):
        out = _sdpa_blockwise(q, k_all.astype(q.dtype),
                              v_all.astype(q.dtype), positions,
                              k_pos, w, scale, bq=min(cfg.attn_block_q, S),
                              bk=min(cfg.attn_block_kv, T))
    else:
        win_ok = (q_pos - k_pos[None, :] < w) | (w == 0)
        mask = mask & win_ok
        out = _sdpa(q, k_all.astype(q.dtype), v_all.astype(q.dtype),
                    mask, scale)
    y = L.linear(p["wo"], out.reshape(B, S, h * hd).astype(x.dtype))
    return y, new_cache


def gqa_apply_paged(p: dict, x: jnp.ndarray, cfg: ArchConfig, *,
                    positions: jnp.ndarray, pool: dict,
                    block_tables: jnp.ndarray):
    """One-token-per-request decode against a block-pool cache
    (launch/paging.py, DESIGN.md §12).

    x: (R, 1, D) — the incoming token for each scheduler slot;
    positions: (R,) int32 — that token's absolute position (== tokens
    already cached for the slot); pool: {"k","v"} of (P, Kh, page, Dh);
    block_tables: (R, M).

    The new K/V is scattered to pool rows ``(block_tables[r, pos//page],
    :, pos % page)`` — inactive slots carry all-zero table rows, so their
    writes land in reserved null block 0 — then attention runs over each
    slot's first ``positions[r] + 1`` cached tokens through
    ops.paged_attention (policy-routed: ref oracle or Pallas kernel).
    Returns (y, new_pool).
    """
    R, S, D = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = L.linear(p["wq"], x).reshape(R, S, h, hd)
    k = L.linear(p["wk"], x).reshape(R, S, kh, hd)
    v = L.linear(p["wv"], x).reshape(R, S, kh, hd)

    cos, sin = L.rope_cos_sin(positions[:, None], hd, cfg.rope_theta)
    q = L.apply_rope(q, cos, sin)                    # per-request (R,1,half)
    k = L.apply_rope(k, cos, sin)

    page = pool["k"].shape[2]
    blk = jnp.take_along_axis(block_tables,
                              (positions // page)[:, None], axis=1)[:, 0]
    off = positions % page                           # (R,) in-block offset
    new_pool = {name: pool[name].at[blk, :, off].set(
                    cur[:, 0].astype(pool[name].dtype))
                for name, cur in (("k", k), ("v", v))}

    from repro.kernels import ops as kops
    out = kops.paged_attention(q[:, 0], new_pool["k"], new_pool["v"],
                               block_tables, positions + 1,
                               policy=arch_policy(cfg))
    y = L.linear(p["wo"], out.reshape(R, 1, h * hd).astype(x.dtype))
    return y, new_pool


# ---------------------------------------------------------- cross-attention

def cross_attn_init(key, cfg: ArchConfig, *, dtype) -> dict:
    """Gated cross-attention onto a stubbed vision/audio stream
    (llama-3.2-vision style: zero-init tanh gate)."""
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = cfg.vision_dim or d
    ks = jax.random.split(key, 5)
    return {
        "wq": L.linear_init(ks[0], d, h * hd, dtype=dtype),
        "wk": L.linear_init(ks[1], src, kh * hd, dtype=dtype),
        "wv": L.linear_init(ks[2], src, kh * hd, dtype=dtype),
        "wo": L.linear_init(ks[3], h * hd, d, dtype=dtype),
        "gate": jnp.zeros((), dtype),
    }


def cross_attn_apply(p: dict, x: jnp.ndarray, src: jnp.ndarray,
                     cfg: ArchConfig) -> jnp.ndarray:
    """x:(B,S,D) attends over src:(B,P,src_dim); no mask (full visibility)."""
    B, S, _ = x.shape
    P = src.shape[1]
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kh
    q = L.linear(p["wq"], x).reshape(B, S, kh, g, hd)
    k = L.linear(p["wk"], src.astype(x.dtype)).reshape(B, P, kh, hd)
    v = L.linear(p["wv"], src.astype(x.dtype)).reshape(B, P, kh, hd)
    mask = jnp.ones((S, P), bool)
    out = _sdpa(q, k, v, mask, 1.0 / jnp.sqrt(hd).astype(jnp.float32))
    y = L.linear(p["wo"], out.reshape(B, S, h * hd).astype(x.dtype))
    return jnp.tanh(p["gate"].astype(x.dtype)) * y


# ------------------------------------------------------------------- MLA ---

def mla_init(key, cfg: ArchConfig, *, dtype) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = L.linear_init(ks[0], d, cfg.q_lora_rank, dtype=dtype)
        p["q_norm"] = L.rmsnorm_init(cfg.q_lora_rank, dtype)
        p["wq_b"] = L.linear_init(ks[1], cfg.q_lora_rank, h * qd, dtype=dtype)
    else:
        p["wq"] = L.linear_init(ks[0], d, h * qd, dtype=dtype)
    p["wkv_a"] = L.linear_init(
        ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype=dtype)
    p["kv_norm"] = L.rmsnorm_init(cfg.kv_lora_rank, dtype)
    p["wkv_b"] = L.linear_init(
        ks[3], cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim),
        dtype=dtype)
    p["wo"] = L.linear_init(ks[4], h * cfg.v_head_dim, d, dtype=dtype)
    return p


def mla_cache_init(cfg: ArchConfig, batch: int, max_len: int, dtype) -> dict:
    """MLA caches the *compressed* latent + shared rope key — its main win."""
    return {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype)}


def mla_apply(p: dict, x: jnp.ndarray, cfg: ArchConfig, *,
              positions: jnp.ndarray, cache: dict | None = None,
              cache_pos=None, window=0):
    B, S, D = x.shape
    h = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    if cfg.q_lora_rank:
        q = L.linear(p["wq_b"], L.rmsnorm(p["q_norm"], L.linear(p["wq_a"], x)))
    else:
        q = L.linear(p["wq"], x)
    q = q.reshape(B, S, h, nd + rd)
    qn, qr = q[..., :nd], q[..., nd:]
    cos, sin = L.rope_cos_sin(positions, rd, cfg.rope_theta)
    qr = L.apply_rope(qr, cos, sin)

    kv_a = L.linear(p["wkv_a"], x)
    c_kv = L.rmsnorm(p["kv_norm"], kv_a[..., :cfg.kv_lora_rank])
    k_rope = L.apply_rope(kv_a[..., None, cfg.kv_lora_rank:], cos, sin)[:, :, 0]

    if cache is not None:
        pos = positions[0] if cache_pos is None else cache_pos
        c_all = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, pos, 0))
        r_all = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, pos, 0))
        new_cache = {"c_kv": c_all, "k_rope": r_all}
        T = c_all.shape[1]
        k_pos = jnp.arange(T)
    else:
        new_cache = None
        c_all, r_all = c_kv, k_rope
        T = S
        k_pos = positions

    kv = L.linear(p["wkv_b"], c_all.astype(x.dtype)).reshape(B, T, h, nd + vd)
    kn, v = kv[..., :nd], kv[..., nd:]

    w = jnp.asarray(window, jnp.int32)
    scale = 1.0 / jnp.sqrt(jnp.float32(nd + rd))
    if cfg.use_blockwise_attn and _use_blockwise(S, T, cfg.attn_block_q,
                                                 cfg.attn_block_kv):
        q_cat = jnp.concatenate([qn, qr], -1)[:, :, :, None, :]  # G=1
        k_cat = jnp.concatenate(
            [kn, jnp.broadcast_to(r_all[:, :, None, :].astype(kn.dtype),
                                  (B, T, h, rd))], -1)
        out = _sdpa_blockwise(q_cat, k_cat, v, positions, k_pos, w, scale,
                              bq=min(cfg.attn_block_q, S),
                              bk=min(cfg.attn_block_kv, T))
        out = out[:, :, :, 0, :]                                 # (B,S,h,vd)
    else:
        q_pos = positions[:, None]
        mask = k_pos[None, :] <= q_pos
        mask = mask & ((q_pos - k_pos[None, :] < w) | (w == 0))
        scores = (jnp.einsum("bshd,bthd->bhst", qn, kn,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshd,btd->bhst", qr, r_all.astype(qr.dtype),
                               preferred_element_type=jnp.float32)) * scale
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhst,bthd->bshd", probs, v)
    y = L.linear(p["wo"], out.reshape(B, S, h * vd).astype(x.dtype))
    return y, new_cache
