"""Pure-jnp oracles for every Pallas kernel in this package.

Each oracle uses the most *direct* formulation (materialized softmax,
step-by-step recurrence) so kernel tests compare two genuinely different
algorithms, not two copies of one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


# -------------------------------------------------------- flash attention --

def attention(q, k, v, *, causal: bool = True, window: int = 0,
              scale: float | None = None):
    """Materialized-softmax attention (the O(S^2)-memory reference).

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D). GQA: Hq a multiple of Hkv.
    window w > 0 keeps keys with q_pos - k_pos < w (absolute positions
    assume q tokens are the last Sq of the Sk context).
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    if scale is None:
        scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qg = q.reshape(B, Hkv, g, Sq, D)
    scores = jnp.einsum("bkgsd,bktd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(Sq)[:, None] + (Sk - Sq)
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= q_pos - k_pos < window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", probs, v.astype(jnp.float32))
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


def attention_grads(q, k, v, g, *, causal: bool = True, window: int = 0,
                    scale: float | None = None):
    """Autodiff gradients of the materialized reference under output
    cotangent ``g`` — the ground truth for the streaming custom-VJP
    kernel pair (kernels/flash_attention.flash_attention_vjp).
    Deliberately routed through ``jax.vjp`` of the direct formulation,
    not the recomputed-p flash recurrence the backward kernels implement,
    so the test compares two genuinely different derivations."""
    _, pull = jax.vjp(
        lambda q_, k_, v_: attention(q_, k_, v_, causal=causal,
                                     window=window, scale=scale), q, k, v)
    return pull(g)


# --------------------------------------------------------- paged attention --

def paged_attention(q, k_pool, v_pool, block_tables, seq_lens, *,
                    scale=None):
    """Gather-then-materialize paged decode attention (the reference).

    q: (R, Hq, D); k/v_pool: (P, Hkv, page, D); block_tables: (R, M);
    seq_lens: (R,) live cached tokens per request. The oracle really
    gathers the whole (R, M*page) context per request and runs a
    materialized masked softmax — deliberately the opposite algorithm to
    the kernel's streamed per-block gather. ``seq_lens[r] == 0`` rows
    return exactly zero (matching the kernel's zero-mass finalize).
    """
    R, hq, d = q.shape
    _, hkv, page, _ = k_pool.shape
    m_slots = block_tables.shape[1]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)

    def gather(pool):
        # (R, M, Hkv, page, D) -> (R, T, Hkv, D), T = M * page
        blocks = jnp.swapaxes(pool[block_tables], 2, 3)
        return blocks.reshape(R, m_slots * page, hkv, d)

    k, v = gather(k_pool), gather(v_pool)
    qg = q.reshape(R, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("rkgd,rtkd->rkgt", qg,
                        k.astype(jnp.float32)) * scale
    live = jnp.arange(m_slots * page)[None, :] < seq_lens[:, None]
    scores = jnp.where(live[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(live[:, None, None], p, 0.0)  # zero-live rows -> zeros
    out = jnp.einsum("rkgt,rtkd->rkgd", p, v.astype(jnp.float32))
    return out.reshape(R, hq, d).astype(q.dtype)


# --------------------------------------------------------------- ssd scan --

def ssd(x, dt, a, b, c, *, initial_state=None):
    """Step-by-step SSM recurrence (the O(S) sequential reference).

    x: (B,S,H,P), dt: (B,S,H), a: (H,), b/c: (B,S,G,N).
    s_t = exp(dt_t a) s_{t-1} + dt_t * (b_t ⊗ x_t);  y_t = c_t · s_t.
    Returns (y: (B,S,H,P), final_state: (B,H,P,N)).
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    bb = jnp.repeat(b, rep, axis=2).astype(jnp.float32)
    cc = jnp.repeat(c, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    s0 = (jnp.zeros((B, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(s, t):
        xt, dtt, bt, ct = t
        da = jnp.exp(dtt * a[None, :])                       # (B,H)
        s = s * da[..., None, None] \
            + jnp.einsum("bh,bhp,bhn->bhpn", dtt, xt, bt)
        y = jnp.einsum("bhpn,bhn->bhp", s, ct)
        return s, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(bb, 1, 0), jnp.moveaxis(cc, 1, 0))
    final, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


def ssd_grads(x, dt, a, b, c, initial_state, g_y, g_state):
    """Autodiff gradients of the sequential-recurrence reference under
    cotangents ``(g_y, g_state)`` — the ground truth for the
    reversed-recurrence custom-VJP kernel pair
    (kernels/ssd_scan.ssd_scan_vjp). Returns
    (dx, ddt, da, db, dc, dinitial_state)."""
    _, pull = jax.vjp(
        lambda *ar: ssd(*ar[:5], initial_state=ar[5]),
        x, dt, a, b, c, initial_state)
    return pull((g_y.astype(x.dtype), g_state.astype(jnp.float32)))


# ------------------------------------------------------------- distill KL --

def distill_kl(teacher_logits, student_logits):
    """Per-row KL(softmax(t) ‖ softmax(s)) with materialized softmaxes.

    (R, V) -> (R,) in float32.
    """
    t = teacher_logits.astype(jnp.float32)
    s = student_logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(t, axis=-1)
    logq = jax.nn.log_softmax(s, axis=-1)
    return jnp.sum(jnp.exp(logp) * (logp - logq), axis=-1)


def distill_kl_grads(teacher_logits, student_logits, g):
    """Autodiff gradients of the materialized reference under per-row
    cotangent ``g`` — the ground truth for the fused custom-VJP kernel
    pair (kernels/distill_kl.distill_kl_vjp). Deliberately routed through
    ``jax.vjp`` of the direct formulation, not the analytic formulas the
    backward kernel implements, so the test compares two genuinely
    different derivations."""
    _, pull = jax.vjp(distill_kl, teacher_logits, student_logits)
    return pull(g.astype(jnp.float32))
