"""Paged decode-attention: one-token queries against a block-pool cache.

Serving (DESIGN.md §12) stores each request's KV history as fixed-size
``page``-token blocks scattered across a shared pool, addressed through a
per-request block table (launch/paging.py). At decode time request ``r``
holds one incoming query token and ``seq_lens[r]`` live cached tokens;
this kernel gathers those k/v blocks *through the block table* and runs
the §9 streaming softmax over them — the pool is never re-packed into a
contiguous per-request cache.

House style (§9), adapted to decode:

  * grid ``(R, Hkv, M)`` — requests x kv-heads x table slots; the k/v
    BlockSpec index maps read the block id from the scalar-prefetched
    table (``pltpu.PrefetchScalarGridSpec``), so the gather IS the
    pipeline's block fetch — no materialized (R, M*page, ...) copy.
    The pool is head-major, ``(P, Hkv, page, D)``, so a fetched block is
    a (page, D) tile: the TPU tiling rule wants the last two block dims
    to be (8, 128)-aligned or whole, which a singleton head dim there
    would break.
  * online (m, l) accumulators in revisited output blocks whose index
    maps ignore the innermost (table-slot) axis; init at ``j == 0``,
    finalize at ``j == M - 1``.
  * the probability block is computed UNDER the mask
    (``jnp.where(live, exp(s - m), 0)``) so table slots past the
    request's live length — including the all-zero table rows of
    inactive scheduler slots — contribute exactly nothing, even when
    every lane in the block is dead (the PR-5 dead-block lesson).

Decode-only, therefore forward-only: serving never differentiates
through the cache, so this kernel has no VJP pair — training-side
attention gradients remain flash_attention's (§9). Unlike the ragged
tails masked in-kernel elsewhere, here *every* block is potentially
ragged (a request rarely fills its last page), so the mask is
unconditional.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30  # finite sentinel: exp(NEG_INF - NEG_INF) stays defined


def _paged_kernel(seq_ref, bt_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  *, scale, page, nb):
    r = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                       # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                       # (page, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    live = kpos < seq_ref[r]                                  # (1, page)
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[0, 0]                                      # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # p under the mask: a fully-dead block (slot past the live length, or
    # the null block of an inactive scheduler slot) must add zero mass,
    # not exp(NEG_INF - NEG_INF) = 1 per lane
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)              # (G, page)
    l_ref[0, 0] = l_ref[0, 0] * alpha + jnp.sum(p, axis=1, keepdims=True)
    o_ref[0, 0] = o_ref[0, 0] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[0, 0] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        o_ref[0, 0] = o_ref[0, 0] / jnp.maximum(l_ref[0, 0], 1e-30)


def paged_attention(q, k_pool, v_pool, block_tables, seq_lens, *,
                    scale=None, interpret=False):
    """Decode attention through a block table.

    q            : (R, Hq, D)   one incoming token per request slot
    k/v_pool     : (P, Hkv, page, D) shared block pools (one layer)
    block_tables : (R, M) int32 pool-block ids; slot ``j`` of request
                   ``r`` holds positions ``[j*page, (j+1)*page)``.
                   Unassigned entries must point at a real pool block
                   (the allocator reserves block 0 for this) — they are
                   masked out by ``seq_lens``, not by id.
    seq_lens     : (R,) int32 live cached tokens per request (the
                   incoming token's k/v included — scatter before call).

    Returns (R, Hq, D) in q.dtype. ``seq_lens[r] == 0`` rows (inactive
    scheduler slots) produce exactly zero.
    """
    R, hq, d = q.shape
    _, hkv, page, _ = k_pool.shape
    m_slots = block_tables.shape[1]
    g = hq // hkv
    assert hq == hkv * g and v_pool.shape == k_pool.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    grid = (R, hkv, m_slots)
    # index maps receive the scalar-prefetch refs last and return BLOCK
    # indices; the k/v maps are the paging gather
    q_spec = pl.BlockSpec((1, 1, g, d), lambda r, h, j, seq, bt: (r, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, page, d),
                           lambda r, h, j, seq, bt: (bt[r, j], h, 0, 0))
    acc_specs = [
        pl.BlockSpec((1, 1, g, d), lambda r, h, j, seq, bt: (r, h, 0, 0)),
        pl.BlockSpec((1, 1, g, 1), lambda r, h, j, seq, bt: (r, h, 0, 0)),
        pl.BlockSpec((1, 1, g, 1), lambda r, h, j, seq, bt: (r, h, 0, 0)),
    ]
    o, _, _ = pl.pallas_call(
        functools.partial(_paged_kernel, scale=float(scale), page=page,
                          nb=m_slots),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec], out_specs=acc_specs),
        out_shape=[jax.ShapeDtypeStruct((R, hkv, g, d), jnp.float32),
                   jax.ShapeDtypeStruct((R, hkv, g, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, hkv, g, 1), jnp.float32)],
        interpret=interpret,
    )(seq_lens.astype(jnp.int32), block_tables.astype(jnp.int32),
      q.reshape(R, hkv, g, d), k_pool, v_pool)
    return o.reshape(R, hq, d).astype(q.dtype)
