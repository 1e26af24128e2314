"""CNN client-model zoo for the paper-faithful DENSE path.

The paper's heterogeneous-FL experiment (Table 2) uses ResNet-18, two small
CNNs, WRN-16-1 and WRN-40-1 on CIFAR10. All are provided here with a common
functional interface; every BatchNorm records (batch μ/σ², running μ/σ²) so
the DENSE generator's L_BN (Eq. 3, DeepInversion-style) can be computed.

API:
  spec = CNNSpec(kind=..., num_classes=..., width=...)
  params = cnn_init(key, spec)
  logits, new_params, bn_stats = cnn_apply(params, spec, x, train=...)
    bn_stats: list of {"mean","var","running_mean","running_var"} per BN,
    new_params: params with updated BN running stats (when train=True).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import layers as L

KINDS = ("cnn1", "cnn2", "resnet18", "wrn16_1", "wrn40_1", "lenet")


@dataclass(frozen=True)
class CNNSpec:
    kind: str = "cnn1"
    num_classes: int = 10
    in_ch: int = 3
    width: float = 1.0          # channel multiplier (tests shrink it)
    image_size: int = 32

    def ch(self, c: int) -> int:
        return max(4, int(round(c * self.width)))


# ------------------------------------------------------------ primitives --

def _cbr_init(key, c_in, c_out, ksize=3):
    return {"conv": L.conv_init(key, c_in, c_out, ksize),
            "bn": L.batchnorm_init(c_out)}


def _cbr(p, x, stats, train, stride=1, relu=True, sample_mask=None):
    pre = L.conv2d(p["conv"], x, stride=stride)
    if sample_mask is None:
        axes = tuple(range(pre.ndim - 1))
        mu = jnp.mean(pre.astype(jnp.float32), axes)
        var = jnp.var(pre.astype(jnp.float32), axes)
    else:
        mu, var = L.masked_batch_moments(pre, sample_mask)
    stats.append({"mean": mu, "var": var,
                  "running_mean": p["bn"]["mean"],
                  "running_var": p["bn"]["var"]})
    y, upd = L.batchnorm(p["bn"], pre, train=train,
                         sample_mask=sample_mask if train else None)
    new_p = {"conv": p["conv"], "bn": {**p["bn"], **upd}}
    return (jax.nn.relu(y) if relu else y), new_p


# ------------------------------------------------------------- small CNNs --

def _cnn_stack_init(key, spec: CNNSpec, chans):
    ks = jax.random.split(key, len(chans) + 1)
    layers = []
    c_prev = spec.in_ch
    for i, c in enumerate(chans):
        layers.append(_cbr_init(ks[i], c_prev, spec.ch(c)))
        c_prev = spec.ch(c)
    feat = max(1, spec.image_size // (2 ** len(chans)))
    fc = L.linear_init(ks[-1], c_prev * feat * feat, spec.num_classes, bias=True)
    return {"layers": layers, "fc": fc}


def _cnn_stack_apply(p, spec, x, train, sample_mask=None):
    stats, new_layers = [], []
    for lp in p["layers"]:
        x, np_ = _cbr(lp, x, stats, train, sample_mask=sample_mask)
        new_layers.append(np_)
        if x.shape[1] > 1:           # stop pooling at 1x1 (tiny test images)
            x = _maxpool2(x)         # strided maximums: ~4x less bandwidth
                                     # than reduce_window on XLA CPU
    x = x.reshape(x.shape[0], -1)
    logits = L.linear(p["fc"], x)
    return logits, {"layers": new_layers, "fc": p["fc"]}, stats


# ------------------------------------------- grouped (m-client) fast path --
#
# Eval-mode forward of m same-spec clients as ONE fused network.
#
# Conv-stack kinds: two static regimes, picked from the (trace-time)
# batch size:
#
#   * small batch (B < _GROUPED_IM2COL_MAX_B): im2col — every conv becomes
#     patch extraction (9 shifted slices) + one client-batched einsum, so
#     the whole ensemble layer is a single wide GEMM. At small B the
#     per-conv fixed costs dominate the unrolled loop and this is ~2x
#     faster on CPU.
#   * large batch: layer 1 is a single conv with client-concatenated
#     output channels (the input is shared, nothing is duplicated), then
#     lax.map over the client axis runs the remaining layers as one
#     compiled body executed m times. At large B all formulations are
#     conv-FLOP-bound; this one never hits XLA-CPU's slow
#     feature_group_count path.
#
# Residual kinds (resnet18, wrn16_1, wrn40_1): cnn_apply's own eval
# forward once per client at every batch size — native convolutions, one
# client at a time. On a TPU v5e this beat stacked im2col GEMMs at every
# batch measured: the stage-2 generator step's teacher pass (forward with
# BN stats and input gradient) over five ResNet-18s took 2.9 / 4.5 / 21 ms
# this way against 4.3 / 11 / 83 ms as im2col GEMMs at B = 8 / 16 / 128.
#
# Up to _GROUPED_UNROLL_MAX_M clients the per-client body runs as a fully
# unrolled lax.scan. Under jax.grad a rolled loop (lax.map) writes every
# client's residuals (BN inputs, ReLU masks) into (m, B, ...) stacks, one
# dynamic-update-slice per residual per client, and the backward reads
# them back: on a v5e that stacking took >= 96 ms of the 650 ms five
# ResNet-18s spend an epoch in the teacher. Unrolled, XLA folds each
# slice of a stack back into the client's own buffer, so no stack is
# built, as in a singleton group. The body is still traced once.
#
# Above the bound, lax.map keeps compile size O(1) in m, and memory O(1)
# in m for the forward alone: under jax.grad the scan stacks every
# client's residuals, unless the chunked teacher's jax.checkpoint re-runs
# them.
#
# All match the unrolled per-client forward to float tolerance.
# ``ensemble.grouped_im2col`` / ``ensemble.grouped_native`` /
# ``ensemble.grouped_unrolled`` (repro.obs) count, at trace time, the
# stacked groups each formulation took.

_GROUPED_IM2COL_MAX_B = 32

# Largest stacked residual group that runs unrolled. Compile time grows
# linearly in the unrolled count: a cold compile of the generator step's
# teacher pass over five ResNet-18s at B = 128, for a v5e, took 77-90 s
# unrolled against 20 s as lax.map, so eight would take about 2 minutes.
# 8 covers the paper's five clients and the five a chip of a 20-client
# group sharded over four chips; larger groups keep lax.map's compile,
# O(1) in m, and the chunked teacher's slices unroll only up to their
# chunk size.
_GROUPED_UNROLL_MAX_M = 8


def _grouped_kernel(w: jnp.ndarray) -> jnp.ndarray:
    """(m, k, k, c_in, c_out) stacked client kernels -> one
    (k, k, c_in, m*c_out) kernel with client-major output channels."""
    m, k1, k2, ci, co = w.shape
    return jnp.transpose(w, (1, 2, 3, 0, 4)).reshape(k1, k2, ci, m * co)


def _bn_eval(bn, pre32, compute_dtype):
    """layers.batchnorm(train=False) on broadcast-ready stat shapes."""
    y = (pre32 - bn["mean"]) * jax.lax.rsqrt(bn["var"] + 1e-5)
    return y.astype(compute_dtype) * bn["scale"].astype(compute_dtype) \
        + bn["bias"].astype(compute_dtype)


def _fold_bn(w: jnp.ndarray, bn) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fold eval-mode BN into the conv: conv(x, w') + t == BN(conv(x, w)).
    Works on stacked ((m,k,k,ci,co), (m,co)) and per-client
    ((k,k,ci,co), (co,)) params. Legal only when the caller does not
    need the pre-BN batch stats."""
    s = bn["scale"] * jax.lax.rsqrt(bn["var"] + 1e-5)
    t = bn["bias"] - bn["mean"] * s
    return w * s[..., None, None, None, :], t


def _maxpool2(h: jnp.ndarray) -> jnp.ndarray:
    """2x2/stride-2 VALID max pool as 3 fused strided maximums —
    reduce_window lowers poorly on XLA CPU (~4x the bandwidth cost)."""
    hh, ww = h.shape[-3] // 2 * 2, h.shape[-2] // 2 * 2
    h = h[..., :hh, :ww, :]
    return jnp.maximum(
        jnp.maximum(h[..., 0::2, 0::2, :], h[..., 0::2, 1::2, :]),
        jnp.maximum(h[..., 1::2, 0::2, :], h[..., 1::2, 1::2, :]))


def _conv3_im2col(h: jnp.ndarray, w: jnp.ndarray, m: int) -> jnp.ndarray:
    """3x3 SAME conv of m per-client kernels as im2col batched GEMMs.

    h: (B,H,W,Ci) shared input, or (m,B,H,W,Ci) per-client.
    w: (m, 3, 3, Ci, Co). -> (m, B, H, W, Co).

    Narrow input (first layer, Ci=3): materialize the full 9Ci patch
    tensor (tiny) and do ONE einsum with K=9Ci — three K=3Ci GEMMs would
    be too thin and pay 3 accumulation passes over the largest output.
    Wide input: full 9Ci patches are memory-bound, so pad once,
    concatenate only the 3 dx-shifts (3Ci) and accumulate 3 GEMMs over
    dy — 3x less copied volume at a still-wide K."""
    hh, ww = h.shape[-3], h.shape[-2]
    pad = [(0, 0)] * (h.ndim - 3) + [(1, 1), (1, 1), (0, 0)]
    hp = jnp.pad(h, pad)
    eq = "bhwf,mfo->mbhwo" if h.ndim == 4 else "mbhwf,mfo->mbhwo"
    if h.shape[-1] < 16:
        patches = jnp.concatenate(
            [hp[..., dy:dy + hh, dx:dx + ww, :]
             for dy in range(3) for dx in range(3)], axis=-1)
        return jnp.einsum(eq, patches,
                          w.reshape(m, -1, w.shape[-1]).astype(h.dtype))
    rows = jnp.concatenate([hp[..., :, dx:dx + ww, :] for dx in range(3)],
                           axis=-1)                    # (..., H+2, W, 3Ci)
    out = None
    for dy in range(3):
        wf = w[:, dy].reshape(m, -1, w.shape[-1]).astype(h.dtype)
        part = jnp.einsum(eq, rows[..., dy:dy + hh, :, :], wf)
        out = part if out is None else out + part
    return out


def _grouped_im2col(stacked, x, m, with_stats):
    stats = []
    h = x
    for lp in stacked["layers"]:
        if with_stats:
            pre32 = _conv3_im2col(h, lp["conv"]["w"], m).astype(jnp.float32)
            stats.append({"mean": jnp.mean(pre32, (1, 2, 3)),
                          "var": jnp.var(pre32, (1, 2, 3)),
                          "running_mean": lp["bn"]["mean"],
                          "running_var": lp["bn"]["var"]})
            bn_b = jax.tree.map(lambda a: a[:, None, None, None, :],
                                lp["bn"])
            h = jax.nn.relu(_bn_eval(bn_b, pre32, x.dtype))
        else:
            wf, t = _fold_bn(lp["conv"]["w"], lp["bn"])
            pre = _conv3_im2col(h, wf, m)
            h = jax.nn.relu(pre + t[:, None, None, None, :].astype(pre.dtype))
        if h.shape[2] > 1:           # stop pooling at 1x1 (tiny test images)
            h = _maxpool2(h)
    feat = h.reshape(m, h.shape[1], -1)
    logits = jnp.einsum("mbf,mfk->mbk", feat,
                        stacked["fc"]["w"].astype(feat.dtype))
    return logits + stacked["fc"]["b"][:, None, :].astype(logits.dtype), stats


def _grouped_conv_scan(stacked, x, m, with_stats):
    # layer 1: shared input -> one conv, client-concatenated out channels
    l1 = stacked["layers"][0]
    if with_stats:
        w1 = l1["conv"]["w"]
    else:
        w1, t1 = _fold_bn(l1["conv"]["w"], l1["bn"])
    pre = jax.lax.conv_general_dilated(
        x, _grouped_kernel(w1).astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    l1_stats = None
    if with_stats:
        pre32 = pre.astype(jnp.float32)
        axes = tuple(range(pre.ndim - 1))
        l1_stats = {"mean": jnp.mean(pre32, axes).reshape(m, -1),
                    "var": jnp.var(pre32, axes).reshape(m, -1),
                    "running_mean": l1["bn"]["mean"],
                    "running_var": l1["bn"]["var"]}
        bn_flat = jax.tree.map(lambda a: a.reshape(-1), l1["bn"])
        h = jax.nn.relu(_bn_eval(bn_flat, pre32, x.dtype))
    else:
        h = jax.nn.relu(pre + t1.reshape(-1).astype(pre.dtype))
    if h.shape[1] > 1:
        h = _maxpool2(h)
    b, hh, ww, mc = h.shape
    h = jnp.transpose(h.reshape(b, hh, ww, m, mc // m),
                      (3, 0, 1, 2, 4))                        # (m,B,H,W,C)

    def one(args):
        hi, layers, fc = args
        st_i = []
        for lp in layers:
            if with_stats:
                w_i = lp["conv"]["w"]
            else:
                w_i, t_i = _fold_bn(lp["conv"]["w"], lp["bn"])
            pre_i = jax.lax.conv_general_dilated(
                hi, w_i.astype(hi.dtype), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            if with_stats:
                p32 = pre_i.astype(jnp.float32)
                ax = tuple(range(p32.ndim - 1))
                st_i.append({"mean": jnp.mean(p32, ax),
                             "var": jnp.var(p32, ax),
                             "running_mean": lp["bn"]["mean"],
                             "running_var": lp["bn"]["var"]})
                hi = jax.nn.relu(_bn_eval(lp["bn"], p32, hi.dtype))
            else:
                hi = jax.nn.relu(pre_i + t_i.astype(pre_i.dtype))
            if hi.shape[1] > 1:
                hi = _maxpool2(hi)
        lg = hi.reshape(hi.shape[0], -1) @ fc["w"].astype(hi.dtype)
        return lg + fc["b"].astype(lg.dtype), st_i

    logits, rest_stats = jax.lax.map(
        one, (h, stacked["layers"][1:], stacked["fc"]))
    if not with_stats:
        return logits, []
    return logits, [l1_stats] + rest_stats


def _grouped_resnet_map(stacked, spec, x, with_stats):
    """Eval-mode forward of stacked same-spec ResNet/WRN clients:
    ``cnn_apply(train=False)`` once per client, so every conv is a native
    convolution in x's dtype — the program a singleton group runs. Up to
    _GROUPED_UNROLL_MAX_M clients (read from the stack's leading dim) a
    fully unrolled ``lax.scan``, above it ``lax.map``. Stats come out in
    ``_basic_apply``'s order (c1, c2, proj) with a leading client dim,
    as vmapping cnn_apply gives them."""
    def one(p):
        logits, _, stats = cnn_apply(p, spec, x, train=False)
        return logits, (stats if with_stats else [])

    m = jax.tree.leaves(stacked)[0].shape[0]
    if m <= _GROUPED_UNROLL_MAX_M:
        obs.count("ensemble.grouped_unrolled")
        _, out = jax.lax.scan(lambda c, p: (c, one(p)), None, stacked,
                              unroll=m)
        return out
    obs.count("ensemble.grouped_native")
    return jax.lax.map(one, stacked)


def cnn_stack_apply_grouped(stacked: dict, spec: CNNSpec, x: jnp.ndarray,
                            m: int, *, with_stats: bool = False):
    """Fused eval-mode forward of m same-spec clients.

    stacked: pytree of client params with a leading client axis
    (ensemble.stack_grouped). Returns (logits (m, B, K), bn_stats) with
    stats leaves carrying the leading client dim — the same contract as
    vmapping cnn_apply; stats is [] when with_stats=False, which also
    lets the conv-stack forwards fold eval-mode BN into the conv kernels
    (_fold_bn).
    Valid for every kind in _CNN_LAYOUT and _RESNET_LAYOUT
    (``is_groupable``). Conv stacks run im2col GEMMs below
    _GROUPED_IM2COL_MAX_B (``_grouped_im2col``) and native convolutions
    from it on (``_grouped_conv_scan``); residual kinds run native
    convolutions per client at every batch, unrolled up to
    _GROUPED_UNROLL_MAX_M clients (``_grouped_resnet_map``).
    """
    if spec.kind in _RESNET_LAYOUT:
        return _grouped_resnet_map(stacked, spec, x, with_stats)
    assert spec.kind in _CNN_LAYOUT, spec.kind
    if x.shape[0] < _GROUPED_IM2COL_MAX_B:
        obs.count("ensemble.grouped_im2col")
        return _grouped_im2col(stacked, x, m, with_stats)
    obs.count("ensemble.grouped_native")
    return _grouped_conv_scan(stacked, x, m, with_stats)


def is_conv_stack(kind: str) -> bool:
    """True for kinds the TRAIN-mode fused path (cnn_stack_train_grouped)
    supports — the plain conv-stack zoo."""
    return kind in _CNN_LAYOUT


def is_groupable(kind: str) -> bool:
    """True for kinds cnn_stack_apply_grouped can fuse in EVAL mode:
    the conv-stack zoo plus the ResNet/WRN kinds."""
    return kind in _CNN_LAYOUT or kind in _RESNET_LAYOUT


def _masked_moments_grouped(pre32: jnp.ndarray, sample_mask):
    """Per-client per-channel (mean, var) of (m, B, H, W, C) activations;
    sample_mask (m, B) restricts to valid rows (None = all valid)."""
    if sample_mask is None:
        return jnp.mean(pre32, (1, 2, 3)), jnp.var(pre32, (1, 2, 3))
    w = sample_mask.astype(jnp.float32)[:, :, None, None, None]
    cnt = jnp.maximum(jnp.sum(w, (1, 2, 3, 4))
                      * (pre32.shape[2] * pre32.shape[3]), 1.0)[:, None]
    mu = jnp.sum(pre32 * w, (1, 2, 3)) / cnt
    var = jnp.sum(jnp.square(pre32 - mu[:, None, None, None, :]) * w,
                  (1, 2, 3)) / cnt
    return mu, var


def cnn_stack_train_grouped(stacked: dict, spec: CNNSpec, x: jnp.ndarray,
                            sample_mask: jnp.ndarray | None = None,
                            momentum: float = 0.9, eps: float = 1e-5):
    """TRAIN-mode forward of m same-spec conv-stack clients as one fused
    network — the local-update analogue of ``cnn_stack_apply_grouped``.

    x: (m, B, H, W, C) per-client batches (unlike eval, nothing is
    shared); sample_mask: (m, B) validity of padded rows. Every conv is
    the im2col batched GEMM (``_conv3_im2col``), deliberately for train:
    the einsum's BACKWARD is again einsums (GEMMs), where both a vmapped
    and a client-concatenated conv formulation lower their kernel
    gradients to XLA CPU's pathological grouped-convolution path (the
    c benchmark table measures the gap). BN batch statistics are masked
    per client and running stats updated exactly as
    ``layers.batchnorm(train=True)`` does, so per-client results match
    ``cnn_apply(..., train=True, sample_mask=...)`` to float tolerance.

    Returns (logits (m, B, K), new_stacked, bn_stats) with stats leaves
    carrying the leading client dim — the same contract as vmapping
    ``cnn_apply``.
    """
    assert spec.kind in _CNN_LAYOUT, spec.kind
    m = x.shape[0]
    h, stats, new_layers = x, [], []
    for lp in stacked["layers"]:
        pre32 = _conv3_im2col(h, lp["conv"]["w"], m).astype(jnp.float32)
        mu, var = _masked_moments_grouped(pre32, sample_mask)
        bn = lp["bn"]
        stats.append({"mean": mu, "var": var,
                      "running_mean": bn["mean"], "running_var": bn["var"]})
        bn_b = {"mean": mu[:, None, None, None, :],
                "var": var[:, None, None, None, :],
                "scale": bn["scale"][:, None, None, None, :],
                "bias": bn["bias"][:, None, None, None, :]}
        y = (pre32 - bn_b["mean"]) * jax.lax.rsqrt(bn_b["var"] + eps)
        y = y.astype(x.dtype) * bn_b["scale"].astype(x.dtype) \
            + bn_b["bias"].astype(x.dtype)
        h = jax.nn.relu(y)
        new_layers.append({"conv": lp["conv"], "bn": {
            **bn, "mean": momentum * bn["mean"] + (1 - momentum) * mu,
            "var": momentum * bn["var"] + (1 - momentum) * var}})
        if h.shape[2] > 1:           # stop pooling at 1x1 (tiny test images)
            h = _maxpool2(h)
    feat = h.reshape(m, h.shape[1], -1)
    logits = jnp.einsum("mbf,mfk->mbk", feat,
                        stacked["fc"]["w"].astype(feat.dtype)) \
        + stacked["fc"]["b"][:, None, :].astype(feat.dtype)
    return logits, {"layers": new_layers, "fc": stacked["fc"]}, stats


# --------------------------------------------------------------- ResNet ----

def _basic_init(key, c_in, c_out, stride):
    ks = jax.random.split(key, 3)
    p = {"c1": _cbr_init(ks[0], c_in, c_out),
         "c2": _cbr_init(ks[1], c_out, c_out)}
    if stride != 1 or c_in != c_out:
        p["proj"] = _cbr_init(ks[2], c_in, c_out, ksize=1)
    return p


def _basic_apply(p, x, stats, train, stride, sample_mask=None):
    y, n1 = _cbr(p["c1"], x, stats, train, stride=stride,
                 sample_mask=sample_mask)
    y, n2 = _cbr(p["c2"], y, stats, train, relu=False,
                 sample_mask=sample_mask)
    new = {"c1": n1, "c2": n2}
    if "proj" in p:
        sc, np_ = _cbr(p["proj"], x, stats, train, stride=stride, relu=False,
                       sample_mask=sample_mask)
        new["proj"] = np_
    else:
        sc = x
    return jax.nn.relu(y + sc), new


def _resnet_init(key, spec: CNNSpec, blocks_per_stage, widths):
    ks = jax.random.split(key, 2 + len(widths) * max(blocks_per_stage))
    i = 0
    p = {"stem": _cbr_init(ks[i], spec.in_ch, spec.ch(widths[0]))}
    i += 1
    stages = []
    c_prev = spec.ch(widths[0])
    for s, w in enumerate(widths):
        blocks = []
        for b in range(blocks_per_stage[s]):
            stride = 2 if (b == 0 and s > 0) else 1
            blocks.append(_basic_init(ks[i], c_prev, spec.ch(w), stride))
            c_prev = spec.ch(w)
            i += 1
        stages.append(blocks)
    p["stages"] = stages
    p["fc"] = L.linear_init(ks[-1], c_prev, spec.num_classes, bias=True)
    return p


def _resnet_apply(p, spec, x, train, blocks_per_stage, sample_mask=None):
    stats = []
    x, new_stem = _cbr(p["stem"], x, stats, train, sample_mask=sample_mask)
    new_stages = []
    for s, blocks in enumerate(p["stages"]):
        new_blocks = []
        for b, bp in enumerate(blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            x, nb = _basic_apply(bp, x, stats, train, stride,
                                 sample_mask=sample_mask)
            new_blocks.append(nb)
        new_stages.append(new_blocks)
    x = jnp.mean(x, axis=(1, 2))
    logits = L.linear(p["fc"], x)
    return logits, {"stem": new_stem, "stages": new_stages, "fc": p["fc"]}, stats


# ------------------------------------------------------------------- API ---

_RESNET_LAYOUT = {
    "resnet18": ([2, 2, 2, 2], [64, 128, 256, 512]),
    "wrn16_1": ([2, 2, 2], [16, 32, 64]),
    "wrn40_1": ([6, 6, 6], [16, 32, 64]),
}
_CNN_LAYOUT = {
    "cnn1": [32, 64, 128],
    "cnn2": [16, 32, 64, 128],
    "lenet": [6, 16],
}


def cnn_init(key, spec: CNNSpec) -> dict:
    if spec.kind in _RESNET_LAYOUT:
        bps, widths = _RESNET_LAYOUT[spec.kind]
        return _resnet_init(key, spec, bps, widths)
    if spec.kind in _CNN_LAYOUT:
        return _cnn_stack_init(key, spec, _CNN_LAYOUT[spec.kind])
    raise ValueError(f"unknown CNN kind {spec.kind!r}")


def cnn_apply(params: dict, spec: CNNSpec, x: jnp.ndarray, *, train: bool,
              sample_mask: jnp.ndarray | None = None):
    """x: (B, H, W, C) in [-1, 1]. Returns (logits, new_params, bn_stats).

    sample_mask (optional, (B,) bool): marks valid rows of a padded
    batch. Train-mode BN statistics (normalization, running-stat updates,
    and the reported bn_stats) are computed over valid rows only, so a
    padded ragged minibatch reproduces its unpadded reference exactly
    (fl/client.local_update_grouped); padded rows still produce logits —
    mask them out of the loss."""
    if spec.kind in _RESNET_LAYOUT:
        bps, _ = _RESNET_LAYOUT[spec.kind]
        return _resnet_apply(params, spec, x, train, bps,
                             sample_mask=sample_mask)
    return _cnn_stack_apply(params, spec, x, train, sample_mask=sample_mask)


def cnn_logits(params: dict, spec: CNNSpec, x: jnp.ndarray) -> jnp.ndarray:
    """Eval-mode logits only."""
    return cnn_apply(params, spec, x, train=False)[0]
