"""Plain reference of the DENSE round, independent of the program.

Straightforward ``jax.numpy`` written from the paper (Zhang et al.,
NeurIPS 2022, Algorithm 1 and Eqs. 1-6) and the layer lists the
configurations name: native convolutions, one client at a time, no
kernels, no grouping, no fusion. It imports nothing of ``repro``.

Every function takes ``prec``: ``"f32"`` computes in float32 with every
matmul and convolution at ``Precision.HIGHEST`` (the reference), and
``"bf16"`` casts parameters, activations and optimizer state to bfloat16
and computes there (the control, the precision below the
configuration's float32). Two faults can be planted: ``half_batch=True``
keeps only the first half of every batch and takes the means over that;
``altered=True`` moves each row of the teacher's answer one class over
where it is made.

Parameter trees have the layout the program's model zoo takes
(``stem``/``stages``/``fc`` for residual kinds, ``layers``/``fc`` for
conv stacks; conv ``w`` in HWIO; BN ``scale``/``bias``/``mean``/``var``),
so the benchmark can hand one tree to both sides.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

RESNET = {"resnet18": ([2, 2, 2, 2], [64, 128, 256, 512]),
          "wrn16_1": ([2, 2, 2], [16, 32, 64]),
          "wrn40_1": ([6, 6, 6], [16, 32, 64])}
CONV_STACK = {"cnn1": [32, 64, 128], "cnn2": [16, 32, 64, 128]}
BN_EPS = 1e-5
BN_MOMENTUM = 0.9
GEN_BASE = 64


def dtype_of(prec: str):
    return jnp.bfloat16 if prec == "bf16" else jnp.float32


def _precision(prec: str):
    return jax.lax.Precision.HIGHEST if prec == "f32" else None


def cast(tree, prec: str):
    """Float leaves to the compute type of ``prec``."""
    dt = dtype_of(prec)
    return jax.tree.map(
        lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


# ------------------------------------------------------------------ init --

def _conv_init(key, c_in, c_out, k):
    w = jax.random.normal(key, (k, k, c_in, c_out), jnp.float32)
    return {"w": w * math.sqrt(2.0 / (c_in * k * k))}


def _bn_init(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,)),
            "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}


def _linear_init(key, d_in, d_out):
    w = jax.random.normal(key, (d_in, d_out), jnp.float32)
    return {"w": w * (1.0 / math.sqrt(d_in)), "b": jnp.zeros((d_out,))}


def _cbr_init(key, c_in, c_out, k=3):
    return {"conv": _conv_init(key, c_in, c_out, k), "bn": _bn_init(c_out)}


def model_init(key, kind: str, *, num_classes: int, in_ch: int,
               image_size: int) -> dict:
    """He-normal convolutions, unit BN, 1/sqrt(fan_in) linear head, drawn
    from ``key`` in the order the configuration's layer list gives."""
    if kind in RESNET:
        blocks, widths = RESNET[kind]
        ks = jax.random.split(key, 2 + len(widths) * max(blocks))
        p = {"stem": _cbr_init(ks[0], in_ch, widths[0])}
        i, c_prev, stages = 1, widths[0], []
        for s, w in enumerate(widths):
            stage = []
            for b in range(blocks[s]):
                stride = 2 if (b == 0 and s > 0) else 1
                kb = jax.random.split(ks[i], 3)
                blk = {"c1": _cbr_init(kb[0], c_prev, w),
                       "c2": _cbr_init(kb[1], w, w)}
                if stride != 1 or c_prev != w:
                    blk["proj"] = _cbr_init(kb[2], c_prev, w, k=1)
                stage.append(blk)
                c_prev, i = w, i + 1
            stages.append(stage)
        p["stages"] = stages
        p["fc"] = _linear_init(ks[-1], c_prev, num_classes)
        return p
    chans = CONV_STACK[kind]
    ks = jax.random.split(key, len(chans) + 1)
    layers, c_prev = [], in_ch
    for i, c in enumerate(chans):
        layers.append(_cbr_init(ks[i], c_prev, c))
        c_prev = c
    feat = max(1, image_size // (2 ** len(chans)))
    return {"layers": layers,
            "fc": _linear_init(ks[-1], c_prev * feat * feat, num_classes)}


def generator_init(key, *, nz: int, img_size: int, out_ch: int) -> dict:
    """DAFL generator: fc -> BN -> 2 x (upsample, conv, BN, lrelu) ->
    conv -> tanh, drawn from ``key`` in that order."""
    s0, b = img_size // 4, GEN_BASE
    ks = jax.random.split(key, 4)
    gbn = lambda c: {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}
    return {"fc": _linear_init(ks[0], nz, 2 * b * s0 * s0), "bn0": gbn(2 * b),
            "c1": _conv_init(ks[1], 2 * b, 2 * b, 3), "bn1": gbn(2 * b),
            "c2": _conv_init(ks[2], 2 * b, b, 3), "bn2": gbn(b),
            "c3": _conv_init(ks[3], b, out_ch, 3)}


# --------------------------------------------------------------- forward --

def _conv(w, x, stride, prec):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_precision(prec))


def _moments(x, w):
    """Per-channel mean and population variance over the rows where
    ``w`` (shape (B,), 0/1) is 1, and over height and width."""
    wb = w.reshape(-1, 1, 1, 1).astype(x.dtype)
    n = jnp.maximum(jnp.sum(w) * x.shape[1] * x.shape[2], 1).astype(x.dtype)
    mu = jnp.sum(x * wb, (0, 1, 2)) / n
    var = jnp.sum(jnp.square(x - mu) * wb, (0, 1, 2)) / n
    return mu, var


def _cbr(p, x, ctx, stride=1, relu=True):
    """conv -> BN (-> relu). ``ctx`` = (train, row weights, prec, stats,
    new running stats): eval mode normalizes with the running stats,
    train mode with the batch's. Both record the batch moments of the
    conv output (the inputs of L_BN)."""
    train, w, prec, stats, running = ctx
    pre = _conv(p["conv"]["w"], x, stride, prec)
    mu, var = _moments(pre, w)
    bn = p["bn"]
    stats.append((mu, var, bn["mean"], bn["var"]))
    if train:
        m, v = mu, var
        running.append((BN_MOMENTUM * bn["mean"] + (1 - BN_MOMENTUM) * mu,
                        BN_MOMENTUM * bn["var"] + (1 - BN_MOMENTUM) * var))
    else:
        m, v = bn["mean"], bn["var"]
    y = (pre - m) * jax.lax.rsqrt(v + BN_EPS) * bn["scale"] + bn["bias"]
    return jax.nn.relu(y) if relu else y


def _maxpool2(h):
    b, hh, ww, c = h.shape
    h = h[:, :hh // 2 * 2, :ww // 2 * 2, :]
    return h.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))


def model_apply(p, kind, x, *, train, prec, rows=None):
    """-> (logits, batch moments per BN layer, new running stats per BN
    layer, in the order the layers run). ``rows`` (B,) weights the
    batch moments; None weighs every row."""
    w = jnp.ones((x.shape[0],), x.dtype) if rows is None else rows
    stats, running = [], []
    ctx = (train, w, prec, stats, running)
    if kind in RESNET:
        h = _cbr(p["stem"], x, ctx)
        for s, stage in enumerate(p["stages"]):
            for b, blk in enumerate(stage):
                stride = 2 if (b == 0 and s > 0) else 1
                y = _cbr(blk["c1"], h, ctx, stride=stride)
                y = _cbr(blk["c2"], y, ctx, relu=False)
                sc = _cbr(blk["proj"], h, ctx, stride=stride, relu=False) \
                    if "proj" in blk else h
                h = jax.nn.relu(y + sc)
        feat = jnp.mean(h, axis=(1, 2))
    else:
        h = x
        for lp in p["layers"]:
            h = _cbr(lp, h, ctx)
            if h.shape[1] > 1:
                h = _maxpool2(h)
        feat = h.reshape(h.shape[0], -1)
    logits = jnp.dot(feat, p["fc"]["w"], precision=_precision(prec)) \
        + p["fc"]["b"]
    return logits, stats, running


def with_running(p, kind, running):
    """``p`` with its BN running stats replaced, in model_apply order."""
    it = iter(running)

    def put(cbr):
        m, v = next(it)
        return {"conv": cbr["conv"], "bn": {**cbr["bn"], "mean": m,
                                            "var": v}}
    if kind in RESNET:
        out = {"stem": put(p["stem"]), "stages": [], "fc": p["fc"]}
        for stage in p["stages"]:
            new_stage = []
            for blk in stage:
                nb = {"c1": put(blk["c1"]), "c2": put(blk["c2"])}
                if "proj" in blk:
                    nb["proj"] = put(blk["proj"])
                new_stage.append(nb)
            out["stages"].append(new_stage)
        return out
    return {"layers": [put(lp) for lp in p["layers"]], "fc": p["fc"]}


def _gbn(p, x):
    """Generator BN: always the batch's own moments."""
    mu = jnp.mean(x, (0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), (0, 1, 2), keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _upsample2(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def generator_apply(p, z, *, img_size, prec):
    s0, b = img_size // 4, GEN_BASE
    h = jnp.dot(z, p["fc"]["w"], precision=_precision(prec)) + p["fc"]["b"]
    h = _gbn(p["bn0"], h.reshape(z.shape[0], s0, s0, 2 * b))
    h = _upsample2(h)
    h = jax.nn.leaky_relu(_gbn(p["bn1"], _conv(p["c1"]["w"], h, 1, prec)),
                          0.2)
    h = _upsample2(h)
    h = jax.nn.leaky_relu(_gbn(p["bn2"], _conv(p["c2"]["w"], h, 1, prec)),
                          0.2)
    return jnp.tanh(_conv(p["c3"]["w"], h, 1, prec))


# ---------------------------------------------------------------- losses --

def _log_softmax(a):
    m = jnp.max(a, -1, keepdims=True)
    s = a - m
    return s - jnp.log(jnp.sum(jnp.exp(s), -1, keepdims=True))


def kl_rows(t, s):
    """Per-row KL(softmax(t) || softmax(s))."""
    lt, ls = _log_softmax(t), _log_softmax(s)
    return jnp.sum(jnp.exp(lt) * (lt - ls), -1)


def cross_entropy(logits, y):
    return -jnp.mean(jnp.take_along_axis(_log_softmax(logits),
                                         y[:, None], -1))


def bn_loss(per_client_stats):
    """Eq. 3: the mean over clients of the summed L2 distances between
    the batch moments of every BN layer's input and its running stats."""
    tot = 0.0
    for stats in per_client_stats:
        for mu, var, rmu, rvar in stats:
            tot = tot + jnp.linalg.norm(mu - rmu) \
                + jnp.linalg.norm(var - rvar)
    return tot / len(per_client_stats)


# ------------------------------------------------------------ optimizers --

def adam_init(p):
    z = jax.tree.map(jnp.zeros_like, p)
    return {"m": z, "v": z, "t": jnp.zeros((), jnp.int32)}


def adam_update(g, st, p, *, lr, b1=0.9, b2=0.999, eps=1e-8):
    t = st["t"] + 1
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, st["m"], g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, st["v"], g)
    tf = t.astype(jnp.float32)
    bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf
    new = jax.tree.map(
        lambda a, m_, v_: (a - lr * (m_ / bc1.astype(a.dtype))
                           / (jnp.sqrt(v_ / bc2.astype(a.dtype)) + eps)
                           ).astype(a.dtype), p, m, v)
    return new, {"m": m, "v": v, "t": t}


def sgd_update(g, mom, p, *, lr, momentum):
    mom = jax.tree.map(lambda m_, g_: (momentum * m_ + g_).astype(m_.dtype),
                       mom, g)
    new = jax.tree.map(lambda a, m_: (a - lr * m_).astype(a.dtype), p, mom)
    return new, mom


def _is_running_stat(path) -> bool:
    return getattr(path[-1], "key", None) in ("mean", "var")


def zero_running_grads(g):
    """Running stats are moved by the batch moments, never by SGD."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if _is_running_stat(path) else a,
        g)


# --------------------------------------------------------------- stage 2 --

def stage2_epoch_fn(cfg: dict, prec: str, half_batch: bool = False,
                    altered: bool = False):
    """One epoch of Algorithm 1 (lines 6-14): T_G Adam steps of the
    generator on L_CE + l1 L_BN + l2 L_div against the unrolled client
    ensemble and the current student, then one SGD step of the student
    on KL(ensemble || student) over the same latent batch.

    -> jitted ``epoch(state, clients, key) -> (state, student grad,
    losses)``, state = (gen, adam state, student, student momentum),
    losses = (the last generator step's loss, the student's loss), as
    the program reports an epoch. One function per configuration and
    variant, so that every seed of a process reuses its compile."""
    return _stage2_epoch_fn(json.dumps(cfg, sort_keys=True), prec,
                            half_batch, altered)


@functools.lru_cache(maxsize=None)
def _stage2_epoch_fn(cfg_json: str, prec: str, half_batch: bool,
                     altered: bool):
    cfg = json.loads(cfg_json)
    kinds, skind = list(cfg["client_kinds"]), cfg["global_kind"]
    b, nz, ncls = cfg["synth_batch"], cfg["nz"], cfg["num_classes"]
    img, dt = cfg["image_size"], dtype_of(prec)
    rows = b // 2 if half_batch else b

    def teacher(clients, x):
        tot, stats = 0.0, []
        for kind, p in zip(kinds, clients):
            lg, st, _ = model_apply(p, kind, x, train=False, prec=prec)
            tot = tot + lg
            stats.append(st)
        avg = tot / len(kinds)
        return (jnp.roll(avg, 1, axis=-1) if altered else avg), stats

    @jax.jit
    def epoch(state, clients, key):
        gen, g_st, stu, s_mom = state
        kz, ky, _ = jax.random.split(key, 3)
        z = jax.random.normal(kz, (b, nz))[:rows].astype(dt)
        y = jax.random.randint(ky, (b,), 0, ncls)[:rows]

        def gen_loss(gp):
            x = generator_apply(gp, z, img_size=img, prec=prec)
            avg, stats = teacher(clients, x)
            s_lg, _, _ = model_apply(stu, skind, x, train=False, prec=prec)
            omega = (jnp.argmax(avg, -1) != jnp.argmax(s_lg, -1)).astype(dt)
            div = -jnp.mean(omega * kl_rows(avg, s_lg))
            return (cross_entropy(avg, y) + cfg["lambda_bn"] * bn_loss(stats)
                    + cfg["lambda_div"] * div)

        def g_body(carry, _):
            gp, gs = carry
            loss, g = jax.value_and_grad(gen_loss)(gp)
            gp, gs = adam_update(g, gs, gp, lr=cfg["g_lr"])
            return (gp, gs), loss

        (gen, g_st), g_losses = jax.lax.scan(g_body, (gen, g_st), None,
                                             length=cfg["t_g"])
        x = jax.lax.stop_gradient(generator_apply(gen, z, img_size=img,
                                                  prec=prec))
        avg, _ = teacher(clients, x)

        def stu_loss(sp):
            lg, _, running = model_apply(sp, skind, x, train=True, prec=prec)
            return jnp.mean(kl_rows(avg, lg)), running

        (s_loss, running), g = jax.value_and_grad(stu_loss,
                                                  has_aux=True)(stu)
        g = zero_running_grads(g)
        stu, s_mom = sgd_update(g, s_mom, stu, lr=cfg["s_lr"],
                                momentum=cfg["s_momentum"])
        return ((gen, g_st, with_running(stu, skind, running), s_mom), g,
                (g_losses[-1], s_loss))

    return epoch


def nudge(tree):
    """Every float value moved one ulp up: a start that differs from the
    given one by rounding alone."""
    return jax.tree.map(
        lambda a: jnp.nextafter(a, jnp.asarray(jnp.inf, a.dtype))
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def stage2_reference(cfg: dict, clients, student, key, epochs: int, *,
                     n_keys: int, prec: str = "f32", half_batch=False,
                     altered=False, nudged=False) -> dict:
    """The student after ``epochs`` epochs from the program's starting
    point: the generator drawn from the first of three keys split from
    ``key`` (Algorithm 1 leaves its init to the implementation; this is
    the DAFL init the configuration states), per-epoch keys split
    ``n_keys`` ways from the third. ``nudged`` starts the student one
    ulp away (the reference against itself, to show how far rounding
    alone carries). -> {"student", "start" (the student it started
    from), "grad0" (the first student gradient), "losses" ((epochs, 2):
    each epoch's last generator loss and student loss)}."""
    k_gen, _, k_ep = jax.random.split(key, 3)
    gen = generator_init(k_gen, nz=cfg["nz"], img_size=cfg["image_size"],
                         out_ch=cfg["in_ch"])
    start = nudge(student) if nudged else student
    stu = cast(start, prec)
    state = (cast(gen, prec), cast(adam_init(gen), prec), stu,
             jax.tree.map(jnp.zeros_like, stu))
    clients = cast(clients, prec)
    epoch = stage2_epoch_fn(cfg, prec, half_batch, altered)
    keys = jax.random.split(k_ep, n_keys)
    g0, losses = None, []
    for e in range(epochs):
        state, g, ls = epoch(state, clients, keys[e])
        losses.append(ls)
        if g0 is None:
            g0 = g
    return {"student": state[2], "grad0": g0, "start": start,
            "losses": np.asarray(jax.device_get(losses), np.float64)}


@functools.lru_cache(maxsize=None)
def _logits_fn(kind: str):
    return jax.jit(lambda p, x: model_apply(p, kind, x, train=True,
                                            prec="f32")[0])


def student_logits(params, kind: str, x):
    """The student's logits on ``x`` at float32 and HIGHEST, its BN
    normalizing with the moments of ``x`` (so that they do not hang on
    the running statistics that the synthetic batches moved)."""
    return np.asarray(jax.device_get(_logits_fn(kind)(cast(params, "f32"),
                                                      x)),
                      np.float64)

