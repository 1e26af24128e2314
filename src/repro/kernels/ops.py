"""Public wrappers around the Pallas kernels, routed by ExecPolicy.

Execution-mode and block-shape selection live in the backend registry
(configs/backend.py, DESIGN.md §11): every wrapper takes ``policy=`` (an
``ExecPolicy``; None resolves registry defaults for the detected
backend). Interpret-mode comes from the registry too — cpu → True
(Pallas executes the kernel body in Python, correctness-validated
against the ``ref.py`` oracles), gpu/tpu → False (compiled), overridable
via ``REPRO_INTERPRET``. The old ``_auto_interpret`` helper special-cased
only tpu, so a gpu backend silently ran every kernel interpreted; the
registry route fixes that.

The old ``interpret=`` / ``block_*=`` / ``vjp_mode=`` kwargs keep
working through a deprecation shim: passing any of them emits a
``DeprecationWarning`` carrying the exact ``policy=`` replacement for
that call, and maps them onto the resolved policy as explicit
overrides. Bare legacy calls keep their historical defaults
(``vjp_mode="autodiff"`` for flash_attention/ssd_scan), so pre-registry
callers see unchanged behavior.

Removal schedule for the shim:
  * PR 8 — ``policy=`` introduced; legacy kwargs deprecated.
  * PR 9 — every in-repo caller migrated to ``policy=`` (the only
    remaining legacy calls are tests/test_backend.py's shim-equivalence
    suite, which pins the shim's behavior until removal); the warning
    now prints the exact replacement snippet.
  * PR 11 — the legacy kwargs are REMOVED: passing them becomes a
    TypeError, and the shim-equivalence tests retire with them.

Every differentiated kernel is a custom-VJP kernel *pair* (DESIGN.md §9):
the forward streams blocks with online accumulators and persists only
per-row/per-tile statistics as residuals, the backward is a second Pallas
kernel that re-streams the blocks to emit the gradients — no quadratic
softmax / state-history intermediate in HBM in either direction.

  * ``distill_kl``     — per-row online-LSE stats; the backward
    re-streams vocab blocks for dL/ds (and optionally dL/dt;
    ``with_teacher_grad=False`` skips that stream for stop-gradient'd
    teachers — DENSE's student step).
  * ``flash_attention``— per-row (m, l) softmax stats; the backward
    re-streams k-blocks (dq) and q-blocks (dk/dv, GQA group-accumulated
    in the revisited output block).
  * ``ssd_scan``       — per-chunk carried states; the backward walks
    the chunks in reverse carrying the state cotangent.

``policy.kernel_vjp`` routes flash_attention/ssd_scan (resolved from
``ArchConfig.kernel_vjp_mode`` by ``configs.backend.arch_policy``,
mirroring the distill-KL mode):

  * ``"ref"``      — the pure-jnp oracle (materialized softmax /
    sequential recurrence), differentiated by jax autodiff. The cpu
    registry default.
  * ``"autodiff"`` — the forward Pallas kernel alone. Forward-only in
    practice: jax's pallas_call JVP rule rejects ``pl.program_id``
    bodies, so differentiating this path raises — kept as the
    no-gradient serving route and as documentation of WHY the kernel
    pairs exist.
  * ``"fused"``    — the custom-VJP kernel pair (the only differentiable
    kernel path; the gpu/tpu registry default).
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.configs import backend as B
from repro.kernels import flash_attention as _fa
from repro.kernels import distill_kl as _kl
from repro.kernels import paged_attention as _pa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ref as _ref

KERNEL_VJP_MODES = B.KERNEL_VJP_MODES
check_kernel_vjp_mode = B.check_kernel_vjp_mode


def _legacy_snippet(kernel, named, interpret, vjp_mode):
    """The exact ``policy=`` expression replacing one legacy call — the
    warning is the migration guide (see the removal schedule above)."""
    expr = "backend.resolve_exec_policy(scfg)"
    if named:
        args = ", ".join(f"{k}={v}" for k, v in named.items())
        expr += f'.override_blocks("{kernel}", {args})'
    repl = {}
    if interpret is not None:
        repl["interpret"] = bool(interpret)
    if vjp_mode is not None:
        repl["kernel_vjp"] = vjp_mode
    if repl:
        args = ", ".join(f"{k}={v!r}" for k, v in repl.items())
        expr += f".replace({args})"
    return expr


def _route(kernel, policy, legacy_blocks, interpret, vjp_mode, shape):
    """Resolve (blocks, interpret, vjp_mode) for one call.

    Pure-policy calls take everything from the registry resolution
    (autotuned blocks when enabled). Legacy kwargs emit a
    DeprecationWarning with the exact replacement snippet and overlay
    the policy: explicitly-passed blocks and interpret win; an unpassed
    legacy ``vjp_mode`` keeps the historical ``"autodiff"`` default
    (NOT the registry mode) so pre-registry call sites keep their exact
    semantics until the PR 11 removal.
    """
    legacy = interpret is not None or vjp_mode is not None \
        or any(v is not None for v in legacy_blocks.values())
    pol = B.resolve_exec_policy(policy)
    if legacy:
        named = {n: v for n, v in legacy_blocks.items() if v is not None}
        warnings.warn(
            f"{kernel}: the interpret=/vjp_mode=/block kwargs are "
            "deprecated and will be removed in PR 11 (schedule in "
            "kernels/ops.py). Replace this call with\n"
            f"    ops.{kernel}(..., policy="
            f"{_legacy_snippet(kernel, named, interpret, vjp_mode)})",
            DeprecationWarning, stacklevel=3)
        if named:
            pol = pol.override_blocks(kernel, **named)
        if interpret is not None:
            pol = pol.replace(interpret=bool(interpret))
        mode = vjp_mode if vjp_mode is not None else \
            (pol.kernel_vjp if policy is not None else "autodiff")
    else:
        mode = pol.kernel_vjp
    check_kernel_vjp_mode(mode)
    if dict(pol.overrides).get(kernel) is None and B.autotune_enabled():
        blocks = B.autotune_blocks(kernel, shape, pol)
    else:
        blocks = pol.blocks_for(kernel, shape)
    return blocks, pol.interpret, mode


def _bwd_blocks(kernel, policy, shape):
    """Backward-kernel block shapes, resolved under the SEPARATE
    ``{kernel}_bwd`` registry entry (same precedence as the forward:
    explicit override > autotuned bucket > registry default). The
    backward's traffic pattern differs from the forward's — re-streaming
    for gradient emission, often ~2x the tensor volume — so its best
    tile is tuned independently (DESIGN.md §13). ssd_scan is the
    documented exception: its residual chunk states are snapshotted at
    FORWARD chunk boundaries, so the backward must walk the identical
    chunk grid and has no entry here (configs/backend.py)."""
    name = kernel + "_bwd"
    pol = B.resolve_exec_policy(policy)
    if dict(pol.overrides).get(name) is None and B.autotune_enabled():
        return B.autotune_blocks(name, shape, pol)
    return pol.blocks_for(name, shape)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret",
                                             "vjp_mode", "bwd_q", "bwd_k"))
def _flash_impl(q, k, v, *, causal, window, block_q, block_k, interpret,
                vjp_mode, bwd_q=None, bwd_k=None):
    if vjp_mode == "ref":
        return _ref.attention(q, k, v, causal=causal, window=window)
    if vjp_mode == "fused":
        return _fa.flash_attention_vjp(q, k, v, causal, window, None,
                                       block_q, block_k, interpret,
                                       bwd_q, bwd_k)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=0, policy=None,
                    block_q=None, block_k=None, interpret=None,
                    vjp_mode=None):
    """Blockwise attention, routed by ``policy.kernel_vjp`` (see module
    docstring). Any Sq/Sk is accepted; tail blocks are masked in-kernel."""
    shape = (q.shape[-2], k.shape[-2])
    (bq, bk), interp, mode = _route(
        "flash_attention", policy,
        {"block_q": block_q, "block_k": block_k}, interpret, vjp_mode,
        shape)
    bwq = bwk = None
    if mode == "fused":
        bwq, bwk = _bwd_blocks("flash_attention", policy, shape)
    return _flash_impl(q, k, v, causal=causal, window=window, block_q=bq,
                       block_k=bk, interpret=interp, vjp_mode=mode,
                       bwd_q=bwq, bwd_k=bwk)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret",
                                             "vjp_mode"))
def _ssd_impl(x, dt, a, b, c, initial_state, *, chunk, interpret, vjp_mode):
    if vjp_mode == "ref":
        return _ref.ssd(x, dt, a, b, c, initial_state=initial_state)
    if vjp_mode == "fused":
        if initial_state is None:
            bsz, _, H, P = x.shape
            initial_state = jnp.zeros((bsz, H, P, b.shape[3]), jnp.float32)
        return _ssd.ssd_scan_vjp(x, dt, a, b, c, initial_state, chunk,
                                 interpret)
    return _ssd.ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=interpret,
                         initial_state=initial_state)


def ssd_scan(x, dt, a, b, c, initial_state=None, *, chunk=None,
             interpret=None, vjp_mode=None, policy=None):
    """SSD chunked scan, routed by ``policy.kernel_vjp`` (see module
    docstring). Any S is accepted (masked tail chunk); ``initial_state``
    (B,H,P,N) seeds the recurrence (prefill→decode handoff)."""
    (ck,), interp, mode = _route(
        "ssd_scan", policy, {"chunk": chunk}, interpret, vjp_mode,
        (x.shape[1],))
    return _ssd_impl(x, dt, a, b, c, initial_state,
                     chunk=min(ck, int(x.shape[1])), interpret=interp,
                     vjp_mode=mode)


# -------------------------------------------- paged_attention (serving) --

@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "vjp_mode"))
def _paged_impl(q, k_pool, v_pool, block_tables, seq_lens, *, scale,
                interpret, vjp_mode):
    if vjp_mode == "ref":
        return _ref.paged_attention(q, k_pool, v_pool, block_tables,
                                    seq_lens, scale=scale)
    return _pa.paged_attention(q, k_pool, v_pool, block_tables, seq_lens,
                               scale=scale, interpret=interpret)


def paged_attention(q, k_pool, v_pool, block_tables, seq_lens, *,
                    scale=None, policy=None):
    """Decode attention through a block-pool cache (DESIGN.md §12).

    q: (R, Hq, D); k/v_pool: (P, Hkv, page, D); block_tables: (R, M);
    seq_lens: (R,). Routed by ``policy.kernel_vjp`` like the training
    kernels — ``"ref"`` runs the gather-then-materialize oracle,
    anything else the streaming Pallas kernel (forward-only by
    construction: decode never differentiates, so there is no VJP pair).

    Unlike the other wrappers this one takes no block kwarg at all,
    legacy or otherwise: the registry's ``page`` entry is a *layout*
    property consumed once, at pool allocation (launch/paging.page_size);
    per-call geometry is fixed by ``k_pool.shape[1]``.
    """
    pol = B.resolve_exec_policy(policy)
    check_kernel_vjp_mode(pol.kernel_vjp)
    return _paged_impl(q, k_pool, v_pool, block_tables, seq_lens,
                       scale=scale, interpret=pol.interpret,
                       vjp_mode=pol.kernel_vjp)


# ------------------------------------------------- distill_kl (fused VJP)

def distill_kl(teacher_logits, student_logits, block_rows=None,
               block_v=None, interpret=None, with_teacher_grad=True, *,
               policy=None):
    """Per-row KL(softmax(t) ‖ softmax(s)), differentiable via the fused
    Pallas backward kernel (kernels/distill_kl.distill_kl_vjp). Any
    (R, V) shape is accepted; tail blocks are masked in-kernel. Always
    the kernel pair — ``policy`` only picks blocks and interpret-mode
    (the ref-vs-fused choice lives one level up, in
    core.losses.softmax_kl)."""
    legacy = block_rows is not None or block_v is not None \
        or interpret is not None
    pol = B.resolve_exec_policy(policy)
    if legacy:
        named = {k: v for k, v in (("block_rows", block_rows),
                                   ("block_v", block_v)) if v is not None}
        warnings.warn(
            "distill_kl: the positional block/interpret args are "
            "deprecated and will be removed in PR 11 (schedule in "
            "kernels/ops.py). Replace this call with\n"
            "    ops.distill_kl(t, s, policy="
            f"{_legacy_snippet('distill_kl', named, interpret, None)})",
            DeprecationWarning, stacklevel=2)
        pol = pol.override_blocks("distill_kl", block_rows=block_rows,
                                  block_v=block_v)
        if interpret is not None:
            pol = pol.replace(interpret=bool(interpret))
    shape = (teacher_logits.shape[0], teacher_logits.shape[1])
    if dict(pol.overrides).get("distill_kl") is None \
            and B.autotune_enabled():
        br, bv = B.autotune_blocks("distill_kl", shape, pol)
    else:
        br, bv = pol.blocks_for("distill_kl", shape)
    bwr, bwv = _bwd_blocks("distill_kl", pol, shape)
    return _kl.distill_kl_vjp(teacher_logits, student_logits, br, bv,
                              pol.interpret, with_teacher_grad, bwr, bwv)


def distill_kl_mean(teacher_logits, student_logits, **kw):
    """Scalar mean-KL convenience (Eq. 6 over a flattened token batch)."""
    r = distill_kl(teacher_logits, student_logits, **kw)
    return jnp.mean(r)
