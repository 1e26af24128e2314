"""Mamba-2 SSD chunked-scan Pallas TPU kernel *pair* (arXiv:2405.21060) —
forward plus a streaming custom-VJP backward (DESIGN.md §9).

Per (batch, head) the sequence is processed in chunks: the intra-chunk
quadratic term is a masked (cl x cl) matmul — MXU work — and the running
SSM state (P x N) is carried across chunk grid steps in a revisited output
block (stays resident in VMEM; the chunk axis is the innermost grid dim,
which Pallas TPU executes sequentially).

This is the TPU-native adaptation of the paper-adjacent GPU scan: no warp
shuffles / selective-scan CUDA kernel, instead blockwise matmuls shaped
for the MXU + a VMEM-resident recurrence.

Differentiation (``ssd_scan_vjp``): jax autodiff cannot transpose this
kernel (the pallas_call JVP rule rejects ``pl.program_id`` bodies), and
an unrolled-recurrence formulation would keep the full (S, P, N) state
history alive between the passes. Instead the forward persists only the
per-chunk *carried* states (nc = ceil(S/chunk) snapshots, the state
entering each chunk) and the backward kernel walks the chunks in REVERSE,
carrying the state cotangent dS in a revisited output block and
recomputing each chunk's intra-chunk quantities from the inputs — the
state history between chunk boundaries is never materialized in either
pass. The dS carry's final content is d(initial_state) for free.

The kernels work head-major — x (B,H,S,P), dt (B,H,S,1), b/c (B,G,S,N)
— so each grid step's block is a whole (chunk, P) / (chunk, N) tile; the
public wrappers keep the model's (B,S,H,...) layout and transpose at the
boundary. In-kernel, per-position vectors are (chunk, 1) columns, and the
within-chunk cumsum is a masked (chunk, chunk) sum: the TPU kernel
compiler has no lowering for ``cumsum``.

Ragged lengths are handled in-kernel: the tail chunk's out-of-range lanes
are zeroed before any arithmetic (dt = 0 ⇒ zero decay and zero state
deposit, so the masked tail contributes nothing to the carried state) —
no S % chunk restriction. ``initial_state`` seeds the recurrence (the
prefill→decode handoff the kernel used to silently drop: it zeroed the
state carry unconditionally while the ``ref.ssd`` oracle honored it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _masks(cl: int):
    """(lower, upper) (cl, cl) masks: lower[l, s] = l >= s."""
    r = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    return r >= c, r <= c


def _load_chunk(x_ref, dt_ref, b_ref, c_ref, ci, *, cl: int, S: int,
                mask_tail: bool):
    """Load one chunk's operands in f32, zeroing the ragged tail lanes.

    Operands are head-major blocks: x (cl, P), b/c (cl, N), and dt as a
    (cl, 1) column. dt = 0 on a masked lane kills every coupling of that
    lane: its decay contribution (da = dt*a = 0 keeps the cumsum flat),
    its intra-chunk column (att carries a dt_s factor) and its state
    deposit (w = dt * e). x/b/c are zeroed too because Pallas pads
    out-of-range reads with undefined values (NaN in interpret mode) and
    0 * NaN = NaN."""
    x = x_ref[0, 0].astype(jnp.float32)                   # (cl, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                 # (cl, 1)
    bmat = b_ref[0, 0].astype(jnp.float32)                # (cl, N)
    cmat = c_ref[0, 0].astype(jnp.float32)                # (cl, N)
    if mask_tail:
        pos = ci * cl + jax.lax.broadcasted_iota(jnp.int32, (cl, 1), 0)
        valid = pos < S
        x = jnp.where(valid, x, 0.0)
        dt = jnp.where(valid, dt, 0.0)
        bmat = jnp.where(valid, bmat, 0.0)
        cmat = jnp.where(valid, cmat, 0.0)
    return x, dt, bmat, cmat


def _chunk_decay(dt, a, cl: int):
    """The chunk's log-decay quantities: cs (cl, 1) within-chunk cumsum
    of dt*a, its last entry cs_end (1, 1), dt as a (1, cl) row, and
    decay[l, s] = e^{cs_l - cs_s} for l >= s (0 above the diagonal)."""
    lower, _ = _masks(cl)
    da = dt * a                                           # (cl, 1)
    cs = jnp.sum(jnp.where(lower, da.T, 0.0), axis=1,
                 keepdims=True)                           # cumsum
    decay = jnp.where(lower, jnp.exp(cs - cs.T), 0.0)
    return cs, cs[cl - 1:], dt.T, decay


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, init_ref, y_ref,
                state_ref, *opt_refs, cl: int, S: int, mask_tail: bool,
                save_states: bool):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[0, 0] = init_ref[0, 0].astype(jnp.float32)

    if save_states:
        # persist the state ENTERING this chunk — the custom-VJP residual
        opt_refs[0][0, 0, 0] = state_ref[0, 0]

    x, dt, bmat, cmat = _load_chunk(x_ref, dt_ref, b_ref, c_ref, ci,
                                    cl=cl, S=S, mask_tail=mask_tail)
    a = a_ref[0].astype(jnp.float32)                      # (1, 1)
    cs, cs_end, dt_row, decay = _chunk_decay(dt, a, cl)

    # intra-chunk: att[l, s] = (c_l . b_s) e^{cs_l - cs_s} dt_s for l >= s
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    att = cb * decay * dt_row
    y_diag = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # inter-chunk: y_off[l] = e^{cs_l} * (c_l . S_prev)
    state = state_ref[0, 0]                               # (P, N)
    y_off = jnp.exp(cs) * jax.lax.dot_general(
        cmat, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # (cl, P)

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: S <- e^{cs_end} S + sum_l e^{cs_end - cs_l} dt_l x_l b_l^T
    w = dt * jnp.exp(cs_end - cs)                         # (cl, 1)
    outer = jax.lax.dot_general(x * w, bmat,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P, N)
    state_ref[0, 0] = jnp.exp(cs_end) * state + outer


def _head_major(x, dt, a, b, c):
    """(B,S,H,P)/(B,S,H)/(H,)/(B,S,G,N) operands -> the kernels' layout:
    x (B,H,S,P), dt (B,H,S,1), a (H,1,1), b/c (B,G,S,N). The TPU tiling
    rule wants the last two block dims (8, 128)-aligned or whole, so the
    one-head-per-step axis must not be one of them."""
    H = a.shape[0]
    return (jnp.swapaxes(x, 1, 2), jnp.swapaxes(dt, 1, 2)[..., None],
            a.reshape(H, 1, 1), jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2))


def ssd_scan(x, dt, a, b, c, *, chunk: int, interpret: bool = False,
             initial_state=None, return_chunk_states: bool = False):
    """SSD forward. x:(B,S,H,P) dt:(B,S,H) a:(H,) b/c:(B,S,G,N).

    Returns (y: (B,S,H,P), final_state: (B,H,P,N)). G groups broadcast over
    heads via the b/c index maps (no repeat materialized). Any S is
    accepted (the tail chunk is masked in-kernel). ``initial_state``
    (B,H,P,N) seeds the recurrence — the prefill→decode handoff.
    ``return_chunk_states=True`` additionally returns the (B,H,nc,P,N)
    per-chunk carried states — the custom-VJP residuals (persisted
    instead of recomputed).
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    cl = min(chunk, S)
    nc = pl.cdiv(S, cl)
    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), jnp.float32)

    out_specs = [
        pl.BlockSpec((1, 1, cl, P), lambda bi, h, ci: (bi, h, ci, 0)),
        pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
    ]
    if return_chunk_states:
        out_specs.append(pl.BlockSpec((1, 1, 1, P, N),
                                      lambda bi, h, ci: (bi, h, ci, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, nc, P, N), jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_ssd_kernel, cl=cl, S=S, mask_tail=(S % cl) != 0,
                          save_states=return_chunk_states),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, cl, P), lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((1, 1, cl, 1), lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, h, ci: (h, 0, 0)),
            pl.BlockSpec((1, 1, cl, N),
                         lambda bi, h, ci: (bi, h * G // H, ci, 0)),
            pl.BlockSpec((1, 1, cl, N),
                         lambda bi, h, ci: (bi, h * G // H, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*_head_major(x, dt, a, b, c), initial_state)
    y = jnp.swapaxes(outs[0], 1, 2)
    if return_chunk_states:
        return y, outs[1], outs[2]
    return y, outs[1]


# ------------------------------------------------------- fused backward --

def _ssd_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, st_ref, dy_ref,
                    dfin_ref, dx_ref, ddt_ref, dbh_ref, dch_ref, dap_ref,
                    dinit_ref, *, cl: int, nc: int, S: int,
                    mask_tail: bool):
    """One chunk of the reversed inter-chunk recurrence.

    The grid's innermost axis runs ci = 0..nc-1 while every index map
    reads chunk rc = nc-1-ci, so the kernel sees the chunks LAST-first.
    ``dinit_ref`` doubles as the dS carry (revisited across ci): it is
    seeded with the final-state cotangent, updated with each chunk's
    d(state-in), and its content after the last grid step IS the
    initial-state gradient. Per-position vectors are (cl, 1) columns."""
    ci = pl.program_id(2)
    rc = nc - 1 - ci                                      # original chunk id

    @pl.when(ci == 0)
    def _init():
        dap_ref[...] = jnp.zeros_like(dap_ref)
        dinit_ref[0, 0] = dfin_ref[0, 0].astype(jnp.float32)

    x, dt, bmat, cmat = _load_chunk(x_ref, dt_ref, b_ref, c_ref, rc,
                                    cl=cl, S=S, mask_tail=mask_tail)
    a = a_ref[0].astype(jnp.float32)                      # (1, 1)
    dy = dy_ref[0, 0].astype(jnp.float32)                 # (cl, P)
    row = jax.lax.broadcasted_iota(jnp.int32, (cl, 1), 0)
    if mask_tail:
        dy = jnp.where(rc * cl + row < S, dy, 0.0)
    s_in = st_ref[0, 0, 0]                                # (P, N)
    ds_out = dinit_ref[0, 0]                              # (P, N)
    _, upper = _masks(cl)

    # ---- recompute the forward chunk quantities (cheap, chunk-local)
    cs, cs_end, dt_row, decay = _chunk_decay(dt, a, cl)
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    att = cb * decay * dt_row
    ecs = jnp.exp(cs)                                     # (cl, 1)
    e_end = jnp.exp(cs_end - cs)
    w = dt * e_end
    y_off = ecs * jax.lax.dot_general(
        cmat, s_in, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # (cl, P)

    # ---- intra-chunk (y_diag = att @ x) cotangents
    datt = jax.lax.dot_general(dy, x, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (cl,cl)
    dx = jax.lax.dot_general(att, dy, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)    # (cl, P)
    dcb = datt * decay * dt_row
    db = jax.lax.dot_general(dcb, cmat, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)    # (cl, N)
    dc = jax.lax.dot_general(dcb, bmat, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)    # (cl, N)
    dseg = datt * cb * dt_row * decay   # decay folds exp(seg) and tril
    dcs = jnp.sum(dseg, axis=1, keepdims=True) \
        - jnp.sum(dseg, axis=0, keepdims=True).T
    ddt_att = jnp.sum(datt * cb * decay, axis=0, keepdims=True).T

    # ---- inter-chunk offset (y_off) cotangents
    dcs = dcs + jnp.sum(dy * y_off, axis=1, keepdims=True)
    dc = dc + ecs * jax.lax.dot_general(
        dy, s_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # ---- state-update (S_out = e^{cs_end} S_in + Σ w_l x_l b_l^T)
    dSb = jax.lax.dot_general(bmat, ds_out, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)   # (cl, P)
    dS_x = jax.lax.dot_general(x, ds_out, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (cl, N)
    dx = dx + w * dSb
    db = db + w * dS_x
    dw = jnp.sum(dS_x * bmat, axis=1, keepdims=True)      # (cl, 1)
    ddt_w = dw * e_end
    dcs = dcs - dw * w
    dcs_end = jnp.sum(dw * w, axis=0, keepdims=True) + jnp.exp(cs_end) \
        * jnp.sum(jnp.sum(ds_out * s_in, axis=1, keepdims=True), axis=0,
                  keepdims=True)
    dcs = dcs + jnp.where(row == cl - 1, dcs_end, 0.0)

    # ---- cumsum transpose + scalar-a partial
    dda = jnp.sum(jnp.where(upper, dcs.T, 0.0), axis=1,
                  keepdims=True)                          # Σ_{l>=t} dcs_l
    ddt = ddt_att + ddt_w + dda * a
    dap_ref[0, 0] = dap_ref[0, 0] + jnp.sum(dda * dt, axis=0, keepdims=True)

    # ---- outputs + carried dS for the previous chunk
    dx_ref[0, 0] = dx
    ddt_ref[0, 0] = ddt
    dbh_ref[0, 0] = db
    dch_ref[0, 0] = dc
    dinit_ref[0, 0] = jnp.exp(cs_end) * ds_out + jax.lax.dot_general(
        dy * ecs, cmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def ssd_scan_bwd(x, dt, a, b, c, chunk_states, dy, dfinal, *,
                 chunk: int, interpret: bool = False):
    """Reversed-recurrence gradients from the per-chunk carried states.

    Returns (dx, ddt, da, db, dc, dinitial_state) in float32. db/dc are
    emitted per head (B,S,H,N) and reduced over each b/c group outside
    the kernel — an input-sized tensor, not a state history.
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    cl = min(chunk, S)
    nc = pl.cdiv(S, cl)

    rev = lambda ci: nc - 1 - ci
    seq = lambda bi, h, ci: (bi, h, rev(ci), 0)
    outs = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, cl=cl, nc=nc, S=S,
                          mask_tail=(S % cl) != 0),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, cl, P), seq),
            pl.BlockSpec((1, 1, cl, 1), seq),
            pl.BlockSpec((1, 1, 1), lambda bi, h, ci: (h, 0, 0)),
            pl.BlockSpec((1, 1, cl, N),
                         lambda bi, h, ci: (bi, h * G // H, rev(ci), 0)),
            pl.BlockSpec((1, 1, cl, N),
                         lambda bi, h, ci: (bi, h * G // H, rev(ci), 0)),
            pl.BlockSpec((1, 1, 1, P, N),
                         lambda bi, h, ci: (bi, h, rev(ci), 0, 0)),
            pl.BlockSpec((1, 1, cl, P), seq),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cl, P), seq),
            pl.BlockSpec((1, 1, cl, 1), seq),
            pl.BlockSpec((1, 1, cl, N), seq),
            pl.BlockSpec((1, 1, cl, N), seq),
            pl.BlockSpec((1, 1, 1, 1), lambda bi, h, ci: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(*_head_major(x, dt, a, b, c), chunk_states,
      jnp.swapaxes(dy.astype(jnp.float32), 1, 2), dfinal.astype(jnp.float32))
    dx, ddt, dbh, dch, dap, dinit = outs
    dx = jnp.swapaxes(dx, 1, 2)
    ddt = jnp.swapaxes(ddt[..., 0], 1, 2)
    da = jnp.sum(dap[:, :, 0, 0], axis=0)                 # (H,)
    db = jnp.sum(jnp.swapaxes(dbh, 1, 2).reshape(B, S, G, rep, N),
                 axis=3)                                  # group-reduce
    dc = jnp.sum(jnp.swapaxes(dch, 1, 2).reshape(B, S, G, rep, N), axis=3)
    return dx, ddt, da, db, dc, dinit


# ------------------------------------------------------------ custom VJP --

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_scan_vjp(x, dt, a, b, c, initial_state, chunk, interpret=False):
    """ssd_scan with the reversed-recurrence Pallas backward (DESIGN.md §9).

    Residual contract: only the inputs (alive anyway) and the per-chunk
    carried states (nc snapshots) are saved — the backward re-streams the
    chunks in reverse, so the (S, P, N) state history never lands in HBM
    in either direction. Also the only *differentiable* kernel path: jax
    autodiff through the forward pallas_call raises (its JVP rule rejects
    ``pl.program_id``). ``initial_state`` must be a concrete (B,H,P,N)
    array (the ops wrapper materializes zeros for callers without one);
    its cotangent falls out of the dS carry for free."""
    return ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=interpret,
                    initial_state=initial_state)


def _vjp_fwd(x, dt, a, b, c, initial_state, chunk, interpret):
    y, final, cstates = ssd_scan(x, dt, a, b, c, chunk=chunk,
                                 interpret=interpret,
                                 initial_state=initial_state,
                                 return_chunk_states=True)
    return (y, final), (x, dt, a, b, c, cstates)


def _vjp_bwd(chunk, interpret, res, g):
    x, dt, a, b, c, cstates = res
    dy, dfinal = g
    dx, ddt, da, db, dc, dinit = ssd_scan_bwd(
        x, dt, a, b, c, cstates, dy, dfinal, chunk=chunk,
        interpret=interpret)
    return (dx.astype(x.dtype), ddt.astype(dt.dtype), da.astype(a.dtype),
            db.astype(b.dtype), dc.astype(c.dtype), dinit)


ssd_scan_vjp.defvjp(_vjp_fwd, _vjp_bwd)
