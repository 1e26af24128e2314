"""Fused distillation-KL Pallas TPU kernel pair — the compute hot-spot
of DENSE stage 2 at LLM scale.

KL(softmax(t) ‖ softmax(s)) per row over very large vocabularies (up to
262 144). The naive jnp formulation materializes two (rows, V) float32
softmax/log-softmax intermediates in HBM (~2 * 4 * R * V bytes); the
*forward* kernel streams vocab blocks through VMEM with online
log-sum-exp accumulators for both distributions plus an online
Σ e^{t−m}(t−s) term:

  KL = S/Z_t − lse_t + lse_s,  where  S = Σ_v e^{t_v − m_t}(t_v − s_v),
                                      Z_t = Σ_v e^{t_v − m_t}.

Accumulators live in revisited output blocks (index maps ignore the vocab
grid axis), the TPU-idiomatic analogue of CUDA shared-memory reductions.
Every per-row statistic travels as an (R, 1) column: a 1-D (R,) array is
tiled by 1024 rows in XLA's TPU layout, which a (block_rows,) block of
256 does not match, so the TPU compiler refuses 1-D row blocks.

The *backward* is the repo's first custom-VJP kernel pair
(``distill_kl_vjp``; DESIGN.md §9): the forward persists only its per-row
accumulators (m_t, Z_t, S, m_s, Z_s — 5 float32 rows, ~20 bytes/row) as
residuals, and a second kernel re-streams the logit blocks to emit

  dL/ds = g ⊙ (softmax(s) − softmax(t))
  dL/dt = g ⊙ p ⊙ ((t − lse_t) − (s − lse_s) − KL),   p = softmax(t)

block-by-block — no (R, V) softmax intermediate ever lands in HBM in
either direction. ``with_teacher_grad=False`` skips the dL/dt stream for
teacher-is-constant call sites (DENSE's student step); the generator-side
losses (stage 1's adversarial L_div) keep it on.

Ragged shapes are handled in-kernel: the vocab tail block is masked to
NEG_INF before any arithmetic (Pallas pads out-of-range block reads with
undefined values), and out-of-range row lanes are dropped by the
out-of-bounds write semantics — no R % block_rows / V % block_v
restriction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0 ** 30


def _mask_cols(t, s, j, bv: int, vocab: int):
    """Mask the out-of-vocab lanes of a (br, bv) block pair to NEG_INF.

    Must run before ANY arithmetic on the blocks: Pallas fills
    out-of-range block reads with undefined values (NaN in interpret
    mode), which would otherwise poison the row reductions. One iota +
    compare shared by both operands; it runs on every vocab block when
    V % bv != 0 (program_id is dynamic, so the tail block can't be
    special-cased at trace time) — VPU-trivial next to the block's
    exp/log work — and divisible vocabs skip it entirely via the static
    ``mask_tail`` flag."""
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    valid = col < vocab
    return jnp.where(valid, t, NEG_INF), jnp.where(valid, s, NEG_INF)


def _kl_fwd_kernel(t_ref, s_ref, kl_ref, mt_ref, zt_ref, st_ref, ms_ref,
                   zs_ref, *, nv: int, bv: int, vocab: int, mask_tail: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        mt_ref[...] = jnp.full_like(mt_ref, NEG_INF)
        zt_ref[...] = jnp.zeros_like(zt_ref)
        st_ref[...] = jnp.zeros_like(st_ref)
        ms_ref[...] = jnp.full_like(ms_ref, NEG_INF)
        zs_ref[...] = jnp.zeros_like(zs_ref)

    t = t_ref[...].astype(jnp.float32)                    # (br, bv)
    s = s_ref[...].astype(jnp.float32)
    if mask_tail:
        t, s = _mask_cols(t, s, j, bv, vocab)

    # online lse + weighted-diff for the teacher; every per-row
    # statistic is a (br, 1) column (the (R, 1) stats layout)
    mt_prev, zt_prev, st_prev = mt_ref[...], zt_ref[...], st_ref[...]
    mt_cur = jnp.max(t, axis=1, keepdims=True)
    mt_new = jnp.maximum(mt_prev, mt_cur)
    at = jnp.exp(mt_prev - mt_new)
    p = jnp.exp(t - mt_new)
    zt_ref[...] = zt_prev * at + jnp.sum(p, axis=1, keepdims=True)
    st_ref[...] = st_prev * at + jnp.sum(p * (t - s), axis=1, keepdims=True)
    mt_ref[...] = mt_new

    # online lse for the student
    ms_prev, zs_prev = ms_ref[...], zs_ref[...]
    ms_cur = jnp.max(s, axis=1, keepdims=True)
    ms_new = jnp.maximum(ms_prev, ms_cur)
    as_ = jnp.exp(ms_prev - ms_new)
    zs_ref[...] = zs_prev * as_ + jnp.sum(jnp.exp(s - ms_new), axis=1,
                                          keepdims=True)
    ms_ref[...] = ms_new

    @pl.when(j == nv - 1)
    def _finalize():
        lse_t = mt_ref[...] + jnp.log(zt_ref[...])
        lse_s = ms_ref[...] + jnp.log(zs_ref[...])
        kl_ref[...] = st_ref[...] / zt_ref[...] - lse_t + lse_s


def _blocking(R: int, V: int, block_rows: int, block_v: int):
    br = min(block_rows, R)
    bv = min(block_v, V)
    nr, nv = pl.cdiv(R, br), pl.cdiv(V, bv)
    return br, bv, nr, nv, (V % bv) != 0


def distill_kl(teacher_logits, student_logits, *, block_rows: int,
               block_v: int, interpret: bool = False,
               return_stats: bool = False):
    """(R, V) x (R, V) -> per-row KL (R,) float32.

    Any (R, V) is accepted: tail blocks are masked in-kernel (ragged
    vocab) and ragged row blocks rely on out-of-bounds writes being
    dropped. With ``return_stats=True`` additionally returns the per-row
    accumulators ``(m_t, Z_t, S, m_s, Z_s)`` the kernel already computed —
    the custom-VJP residuals (persisted instead of recomputed).
    """
    R, V = teacher_logits.shape
    br, bv, nr, nv, mask_tail = _blocking(R, V, block_rows, block_v)

    row_map = lambda i, j: (i, 0)
    stats = pl.pallas_call(
        functools.partial(_kl_fwd_kernel, nv=nv, bv=bv, vocab=V,
                          mask_tail=mask_tail),
        grid=(nr, nv),
        in_specs=[pl.BlockSpec((br, bv), lambda i, j: (i, j)),
                  pl.BlockSpec((br, bv), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((br, 1), row_map)] * 6,
        out_shape=[jax.ShapeDtypeStruct((R, 1), jnp.float32)] * 6,
        interpret=interpret,
        name="distill_kl_fwd",
    )(teacher_logits, student_logits)
    kl, mt, zt, st, ms, zs = (a[:, 0] for a in stats)
    if return_stats:
        return kl, (mt, zt, st, ms, zs)
    return kl


# ------------------------------------------------------- fused backward --

def _kl_bwd_kernel(t_ref, s_ref, lt_ref, ls_ref, kl_ref, g_ref, *out_refs,
                   bv: int, vocab: int, mask_tail: bool, with_dt: bool):
    """One (br, bv) block of the analytic KL gradients.

    Purely elementwise given the per-row statistics — no accumulators, so
    the grid is embarrassingly parallel (unlike the forward's sequential
    vocab sweep)."""
    j = pl.program_id(1)
    t = t_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    if mask_tail:
        t, s = _mask_cols(t, s, j, bv, vocab)
    lt = lt_ref[...]                     # lse_t, (br, 1)
    ls = ls_ref[...]
    g = g_ref[...]
    p = jnp.exp(t - lt)                  # softmax(t) block
    q = jnp.exp(s - ls)                  # softmax(s) block
    ds_ref = out_refs[-1]
    ds_ref[...] = (g * (q - p)).astype(ds_ref.dtype)
    if with_dt:
        dt_ref = out_refs[0]
        kl = kl_ref[...]
        dt_ref[...] = (g * p * ((t - lt) - (s - ls) - kl)).astype(dt_ref.dtype)


def distill_kl_bwd(teacher_logits, student_logits, lse_t, lse_s, kl, g, *,
                   block_rows: int, block_v: int,
                   interpret: bool = False, with_teacher_grad: bool = True):
    """Stream the KL gradients from per-row stats: returns (dt, ds); dt is
    None when with_teacher_grad=False (the dL/dt stream is skipped
    entirely, not computed-and-zeroed)."""
    R, V = teacher_logits.shape
    br, bv, nr, nv, mask_tail = _blocking(R, V, block_rows, block_v)

    row_map = lambda i, j: (i, 0)
    blk_map = lambda i, j: (i, j)
    out_specs = [pl.BlockSpec((br, bv), blk_map)]
    out_shape = [jax.ShapeDtypeStruct((R, V), student_logits.dtype)]
    if with_teacher_grad:
        out_specs = [pl.BlockSpec((br, bv), blk_map)] + out_specs
        out_shape = [jax.ShapeDtypeStruct((R, V), teacher_logits.dtype)] \
            + out_shape
    outs = pl.pallas_call(
        functools.partial(_kl_bwd_kernel, bv=bv, vocab=V,
                          mask_tail=mask_tail, with_dt=with_teacher_grad),
        grid=(nr, nv),
        in_specs=[pl.BlockSpec((br, bv), blk_map),
                  pl.BlockSpec((br, bv), blk_map)]
        + [pl.BlockSpec((br, 1), row_map)] * 4,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="distill_kl_bwd",
    )(teacher_logits, student_logits,
      *(a.reshape(R, 1) for a in (lse_t, lse_s, kl, g)))
    if with_teacher_grad:
        return outs[0], outs[1]
    return None, outs[0]


# ------------------------------------------------------------ custom VJP --

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def distill_kl_vjp(teacher_logits, student_logits, block_rows, block_v,
                   interpret=False, with_teacher_grad=True,
                   bwd_rows=None, bwd_v=None):
    """distill_kl with the fused Pallas backward (DESIGN.md §9).

    Residual contract: only the inputs (alive anyway) and the per-row
    forward accumulators are saved — the backward re-streams the logit
    blocks, so neither pass materializes an (R, V) softmax in HBM.
    ``with_teacher_grad=False`` declares the teacher cotangent unused
    (e.g. stage 2's stop-gradient'd ensemble): the backward skips the
    dL/dt kernel stream and returns a zeros cotangent in its place —
    under jit (every repo call site) XLA dead-code-eliminates it when
    the teacher really is a non-differentiated input; an eager caller
    that actually consumes the teacher gradient should keep
    ``with_teacher_grad=True``.

    ``bwd_rows``/``bwd_v`` (None -> reuse the forward blocks) give the
    backward kernel its OWN block shapes: it streams up to 2x the
    forward's tensor traffic (dt and ds emission) with a different
    arithmetic intensity, so its best tile need not be the forward's —
    the registry/autotuner resolve them under the separate
    ``distill_kl_bwd`` kernel entry (configs/backend.py, DESIGN.md §11).
    """
    return distill_kl(teacher_logits, student_logits, block_rows=block_rows,
                      block_v=block_v, interpret=interpret)


def _vjp_fwd(t, s, block_rows, block_v, interpret, with_teacher_grad,
             bwd_rows, bwd_v):
    kl, (mt, zt, _st, ms, zs) = distill_kl(
        t, s, block_rows=block_rows, block_v=block_v, interpret=interpret,
        return_stats=True)
    # fold (m, Z) -> lse once per row; S already folded into kl
    return kl, (t, s, mt + jnp.log(zt), ms + jnp.log(zs), kl)


def _vjp_bwd(block_rows, block_v, interpret, with_teacher_grad,
             bwd_rows, bwd_v, res, g):
    t, s, lse_t, lse_s, kl = res
    dt, ds = distill_kl_bwd(t, s, lse_t, lse_s, kl,
                            g.astype(jnp.float32),
                            block_rows=bwd_rows if bwd_rows else block_rows,
                            block_v=bwd_v if bwd_v else block_v,
                            interpret=interpret,
                            with_teacher_grad=with_teacher_grad)
    if dt is None:
        # teacher declared constant by the caller: zeros cotangent — a
        # concrete array here (custom_vjp must return a full pytree), but
        # DCE'd by XLA under jit when the teacher is non-differentiated
        dt = jnp.zeros_like(t)
    return dt, ds


distill_kl_vjp.defvjp(_vjp_fwd, _vjp_bwd)
