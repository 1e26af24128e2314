"""Blockwise (flash) attention Pallas TPU kernel *pair* — forward plus a
streaming custom-VJP backward (DESIGN.md §9).

Online-softmax attention with GQA and sliding-window support. VMEM
footprint per grid step is O(bq*D + bk*D + bq*bk) instead of O(Sq*Sk).

TPU adaptation notes (DESIGN.md §3): running max/denominator and the
output accumulator live in *revisited output blocks* — their index maps
ignore the k-block grid axis, so Pallas keeps them resident in VMEM across
the innermost loop (the TPU-idiomatic replacement for CUDA shared-memory
accumulators). Block sizes default to MXU-friendly multiples of 128.

Differentiation (``flash_attention_vjp``): jax autodiff cannot transpose
this kernel — the pallas_call JVP rule rejects ``pl.program_id`` bodies
outright, and even where it applied it would rematerialize the (Sq, Sk)
probability matrix the forward streams to avoid. Instead the forward
persists only the per-row softmax statistic ``lse = m + log l`` (plus the
f32 output, consumed as ``delta = Σ_d dO⊙O``) and two backward kernels
re-stream the blocks with the standard recomputed-p flash recurrence:

  p    = exp(q·kᵀ·scale − lse)            (recomputed per block)
  dv  += pᵀ · dO
  ds   = p ⊙ (dO·vᵀ − delta)
  dq  += ds · k · scale                    (k-block stream per q row)
  dk  += dsᵀ · q · scale                   (q-block stream per k row)

— no (Sq, Sk) intermediate in HBM in either direction. GQA: the dk/dv
grid walks the g query heads of each kv head in its innermost axis, so
group accumulation happens in the revisited output block.

Ragged shapes are handled in-kernel like ``distill_kl``: tail k-blocks
are masked to NEG_INF before any arithmetic and garbage tail *values* are
zeroed (Pallas pads out-of-range block reads with undefined values — NaN
in interpret mode), ragged q rows rely on out-of-bounds writes being
dropped — no Sq % bq / Sk % bk restriction. A block whose keys are ALL
masked (short sliding window, tail) contributes exactly nothing: ``p`` is
forced to zero under the mask. The former ``exp(NEG_INF − NEG_INF) = 1``
lanes inflated ``l`` while ``m == NEG_INF`` — washed out of ``o`` by
alpha underflow once a live block arrived, but corrupting the persisted
``(m, l)`` statistic (the residual the backward's recomputed ``p``
divides by) for rows with no live key at all (causal with Sq > Sk,
ragged tails): ``l`` is now exactly the live softmax mass, zero for such
rows, pinning their ``lse`` to NEG_INF and their backward contribution
to zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0 ** 30


def _block_mask(i, j, *, bq: int, bk: int, causal: bool, window: int,
                seq_off: int, sq: int, sk: int, mask_q_tail: bool,
                mask_k_tail: bool):
    """(bq, bk) validity mask for q-block i vs k-block j.

    Shared by the forward and both backward kernels so the three streams
    see the identical mask (causal, sliding window, and — when the
    sequence is not a block multiple — the ragged tail lanes)."""
    q_idx = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    q_pos = q_idx + seq_off
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= k_idx <= q_pos
    if window:
        mask &= q_pos - k_idx < window
    if mask_q_tail:
        mask &= q_idx < sq
    if mask_k_tail:
        mask &= k_idx < sk
    return mask


def _zero_tail_rows(x, blk, bsz: int, n: int):
    """Zero the out-of-range rows of a (bsz, D) block: Pallas fills OOB
    reads with undefined values (NaN in interpret mode) which would
    otherwise poison cross-row reductions/matmuls."""
    idx = blk * bsz + jax.lax.broadcasted_iota(jnp.int32, (bsz, 1), 0)
    return jnp.where(idx < n, x, 0.0)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                  scale: float, bq: int, bk: int, nk: int, causal: bool,
                  window: int, seq_off: int, sq: int, sk: int,
                  mask_k_tail: bool):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                     # (bq, D)
    k = k_ref[0].astype(jnp.float32)                     # (bk, D)
    v = v_ref[0].astype(jnp.float32)                     # (bk, D)
    if mask_k_tail:
        # garbage v rows meet exact-zero p lanes below, but 0 * NaN = NaN
        v = _zero_tail_rows(v, j, bk, sk)
    # ragged q rows need no zeroing here: every op below is row-local, so
    # their NaNs stay in rows the out-of-bounds write drops

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    i = pl.program_id(1)
    # mask_q_tail stays False here: the forward is row-local, so ragged q
    # rows quarantine their own NaNs and are dropped on write
    mask = _block_mask(i, j, bq=bq, bk=bk, causal=causal, window=window,
                       seq_off=seq_off, sq=sq, sk=sk,
                       mask_q_tail=False, mask_k_tail=mask_k_tail)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[0]                                    # (bq, 1)
    l_prev = l_ref[0]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    # p under the mask, NOT exp(s - m_new): a fully-masked block (short
    # window / ragged tail) has m_new == NEG_INF, where exp(s - m_new)
    # = exp(0) = 1 per lane — inflating l by bk per dead block while no
    # live key has been seen. Harmless to o (alpha underflows the stale l
    # away at the first live block; never-live rows emit 0 either way)
    # but fatal to the persisted stats: l must be the exact live mass for
    # lse = m + log l to be the backward's softmax denominator, and
    # exactly 0 for never-live rows so their lse pins to NEG_INF
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    o_ref[0] = o_ref[0] * alpha \
        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    m_ref[0] = m_new
    l_ref[0] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = o_ref[0] / jnp.maximum(l_ref[0], 1e-30)


def _blocking(Sq: int, Sk: int, block_q: int, block_k: int):
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq, nk = pl.cdiv(Sq, bq), pl.cdiv(Sk, bk)
    return bq, bk, nq, nk, (Sq % bq) != 0, (Sk % bk) != 0


def _fwd_flat(qf, kf, vf, *, Hq, Hkv, causal, window, scale, block_q,
              block_k, interpret):
    """Flattened-head forward: qf (B*Hq, Sq, D), kf/vf (B*Hkv, Sk, D)
    -> (o, m, l) with o float32 (the per-row stats are the VJP residual)."""
    BH, Sq, D = qf.shape
    Sk = kf.shape[1]
    g = Hq // Hkv
    bq, bk, nq, nk, mq, mk = _blocking(Sq, Sk, block_q, block_k)

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        return ((bh // Hq) * Hkv + (bh % Hq) // g, j, 0)

    def ml_map(bh, i, j):
        return (bh, i, 0)

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, bq=bq, bk=bk, nk=nk,
                          causal=causal, window=window, seq_off=Sk - Sq,
                          sq=Sq, sk=Sk, mask_k_tail=mk),
        grid=(BH, nq, nk),
        in_specs=[pl.BlockSpec((1, bq, D), q_map),
                  pl.BlockSpec((1, bk, D), kv_map),
                  pl.BlockSpec((1, bk, D), kv_map)],
        out_specs=[pl.BlockSpec((1, bq, D), q_map),
                   pl.BlockSpec((1, bq, 1), ml_map),
                   pl.BlockSpec((1, bq, 1), ml_map)],
        out_shape=[jax.ShapeDtypeStruct((BH, Sq, D), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int,
                    block_k: int, interpret: bool = False,
                    return_stats: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D).

    GQA handled by the k/v index maps (Hq = g * Hkv). ``window`` keeps
    keys with q_pos - k_pos < window (q tokens are the last Sq of Sk).
    Any Sq/Sk is accepted: tail blocks are masked in-kernel, ragged q
    rows rely on out-of-bounds writes being dropped. With
    ``return_stats=True`` additionally returns ``(o_f32, lse)`` on the
    flattened (B*Hq, ...) view — the custom-VJP residuals (persisted
    instead of recomputed).
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    if scale is None:
        scale = float(1.0 / (D ** 0.5))
    out, m, l = _fwd_flat(q.reshape(B * Hq, Sq, D),
                          k.reshape(B * Hkv, Sk, D),
                          v.reshape(B * Hkv, Sk, D),
                          Hq=Hq, Hkv=Hkv, causal=causal, window=window,
                          scale=scale, block_q=block_q, block_k=block_k,
                          interpret=interpret)
    if return_stats:
        # fold (m, l) -> lse once per row; rows that never saw a live key
        # (l == 0) pin to NEG_INF so the backward's exp stays finite
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
        lse = lse[..., 0]
        return out.reshape(B, Hq, Sq, D).astype(q.dtype), out, lse
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


# ------------------------------------------------------- fused backward --

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                         dq_ref, *, scale: float, bq: int, bk: int,
                         causal: bool, window: int, seq_off: int, sq: int,
                         sk: int, mask_k_tail: bool):
    """dq for one q block, streaming k blocks (grid = fwd grid). Row-local
    except the k/v reads, so ragged q rows self-quarantine as in the
    forward."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]                                     # (bq, 1)
    delta = d_ref[0]
    if mask_k_tail:
        k = _zero_tail_rows(k, j, bk, sk)
        v = _zero_tail_rows(v, j, bk, sk)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _block_mask(i, j, bq=bq, bk=bk, causal=causal, window=window,
                       seq_off=seq_off, sq=sq, sk=sk,
                       mask_q_tail=False, mask_k_tail=mask_k_tail)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dq_ref[0] = dq_ref[0] + jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                          dk_ref, dv_ref, *, scale: float, bq: int,
                          bk: int, nq: int, causal: bool, window: int,
                          seq_off: int, sq: int, sk: int,
                          mask_q_tail: bool, mask_k_tail: bool):
    """dk/dv for one k block, streaming q blocks. The innermost grid axis
    enumerates (query head in group) x (q block), so GQA group summation
    lands in the revisited dk/dv blocks. Garbage q-tail rows WOULD cross
    rows here (they enter k-row reductions), so they are zeroed and
    masked, unlike the row-local kernels."""
    j, t = pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    i = t % nq
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]
    delta = d_ref[0]
    if mask_q_tail:
        q = _zero_tail_rows(q, i, bq, sq)
        do = _zero_tail_rows(do, i, bq, sq)
        row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        lse = jnp.where(row < sq, lse, 0.0)
        delta = jnp.where(row < sq, delta, 0.0)
    if mask_k_tail:
        k = _zero_tail_rows(k, j, bk, sk)
        v = _zero_tail_rows(v, j, bk, sk)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _block_mask(i, j, bq=bq, bk=bk, causal=causal, window=window,
                       seq_off=seq_off, sq=sq, sk=sk,
                       mask_q_tail=mask_q_tail, mask_k_tail=mask_k_tail)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dv_ref[0] = dv_ref[0] + jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dk_ref[0] = dk_ref[0] + jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def flash_attention_bwd(q, k, v, o_f32, lse, do, *, causal: bool = True,
                        window: int = 0, scale: float | None = None,
                        block_q: int, block_k: int,
                        interpret: bool = False):
    """Stream the attention gradients from per-row stats: (dq, dk, dv).

    o_f32/lse are the forward's flattened residuals; the (Sq, Sk)
    probability matrix is recomputed block-by-block, never materialized.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    if scale is None:
        scale = float(1.0 / (D ** 0.5))
    bq, bk, nq, nk, mq, mk = _blocking(Sq, Sk, block_q, block_k)

    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, D)
    dof = do.astype(jnp.float32).reshape(B * Hq, Sq, D)
    # per-row stats travel as (B*Hq, Sq, 1) columns (TPU block tiling)
    delta = jnp.sum(dof * o_f32, axis=-1, keepdims=True)
    lse = lse[..., None]

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        return ((bh // Hq) * Hkv + (bh % Hq) // g, j, 0)

    def ml_map(bh, i, j):
        return (bh, i, 0)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal, window=window, seq_off=Sk - Sq,
                          sq=Sq, sk=Sk, mask_k_tail=mk),
        grid=(B * Hq, nq, nk),
        in_specs=[pl.BlockSpec((1, bq, D), q_map),
                  pl.BlockSpec((1, bk, D), kv_map),
                  pl.BlockSpec((1, bk, D), kv_map),
                  pl.BlockSpec((1, bq, D), q_map),
                  pl.BlockSpec((1, bq, 1), ml_map),
                  pl.BlockSpec((1, bq, 1), ml_map)],
        out_specs=[pl.BlockSpec((1, bq, D), q_map)],
        out_shape=[jax.ShapeDtypeStruct((B * Hq, Sq, D), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)[0]

    # dk/dv: grid (kv head, k block, g * nq) — the t axis walks every
    # (query head of the group, q block) pair with the dk/dv block
    # resident, so GQA accumulation never materializes per-q-head copies
    def qt_map(bh, j, t):
        return ((bh // Hkv) * Hq + (bh % Hkv) * g + t // nq, t % nq, 0)

    def kt_map(bh, j, t):
        return (bh, j, 0)

    def mlt_map(bh, j, t):
        return ((bh // Hkv) * Hq + (bh % Hkv) * g + t // nq, t % nq, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, bq=bq,
                          bk=bk, nq=nq, causal=causal, window=window,
                          seq_off=Sk - Sq, sq=Sq, sk=Sk, mask_q_tail=mq,
                          mask_k_tail=mk),
        grid=(B * Hkv, nk, g * nq),
        in_specs=[pl.BlockSpec((1, bq, D), qt_map),
                  pl.BlockSpec((1, bk, D), kt_map),
                  pl.BlockSpec((1, bk, D), kt_map),
                  pl.BlockSpec((1, bq, D), qt_map),
                  pl.BlockSpec((1, bq, 1), mlt_map),
                  pl.BlockSpec((1, bq, 1), mlt_map)],
        out_specs=[pl.BlockSpec((1, bk, D), kt_map),
                   pl.BlockSpec((1, bk, D), kt_map)],
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, Sk, D), jnp.float32),
                   jax.ShapeDtypeStruct((B * Hkv, Sk, D), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    return (dq.reshape(B, Hq, Sq, D).astype(q.dtype),
            dk.reshape(B, Hkv, Sk, D).astype(k.dtype),
            dv.reshape(B, Hkv, Sk, D).astype(v.dtype))


# ------------------------------------------------------------ custom VJP --

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def flash_attention_vjp(q, k, v, causal, window, scale,
                        block_q, block_k, interpret=False,
                        bwd_q=None, bwd_k=None):
    """flash_attention with the streaming Pallas backward (DESIGN.md §9).

    Residual contract: only the inputs (alive anyway), the f32 output and
    the per-row ``lse`` statistic are saved — the backward re-streams the
    q/k blocks, so neither pass materializes the (Sq, Sk) probability
    matrix in HBM. Also the only *differentiable* kernel path: jax
    autodiff through the forward pallas_call raises (its JVP rule rejects
    ``pl.program_id``).

    ``bwd_q``/``bwd_k`` (None -> reuse the forward blocks) give the
    backward its OWN tile shapes: the dq pass streams k-blocks per
    q-block while the dk/dv pass streams q-blocks per k-block, a
    different traffic pattern from the forward — the registry/autotuner
    resolve them under the separate ``flash_attention_bwd`` kernel entry
    (configs/backend.py, DESIGN.md §11)."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           scale=scale, block_q=block_q, block_k=block_k,
                           interpret=interpret)


def _vjp_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret,
             bwd_q, bwd_k):
    out, o_f32, lse = flash_attention(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        return_stats=True)
    return out, (q, k, v, o_f32, lse)


def _vjp_bwd(causal, window, scale, block_q, block_k, interpret,
             bwd_q, bwd_k, res, g):
    q, k, v, o_f32, lse = res
    return flash_attention_bwd(q, k, v, o_f32, lse, g, causal=causal,
                               window=window, scale=scale,
                               block_q=bwd_q if bwd_q else block_q,
                               block_k=bwd_k if bwd_k else block_k,
                               interpret=interpret)


flash_attention_vjp.defvjp(_vjp_fwd, _vjp_bwd)
