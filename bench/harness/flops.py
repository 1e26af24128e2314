"""Operations and bytes the algorithm needs, counted from shapes.

The counts are of the algorithm's work, whatever implements it: a
convolution or linear layer costs 2*N*H_out*W_out*C_in*C_out*k*k for
its forward pass, the same again for the gradient of its input, and the
same again for the gradient of its weights. The input gradient of a
layer whose input needs none (a network's first layer when its input is
data, not a generated image) is not counted. BatchNorm, activations,
pooling, the loss and the optimizer update are elementwise and are not
counted. Nothing recomputed counts.
"""
from __future__ import annotations

RESNET = {"resnet18": ([2, 2, 2, 2], [64, 128, 256, 512]),
          "wrn16_1": ([2, 2, 2], [16, 32, 64]),
          "wrn40_1": ([6, 6, 6], [16, 32, 64])}
CONV_STACK = {"cnn1": [32, 64, 128], "cnn2": [16, 32, 64, 128]}
GEN_BASE = 64
F32 = 4


def conv_flops(n, h_out, w_out, c_in, c_out, k) -> int:
    """Forward operations of one k x k convolution (a linear layer is
    k = 1 at 1 x 1)."""
    return 2 * n * h_out * w_out * c_in * c_out * k * k


def layers(kind: str, *, image_size: int, in_ch: int, num_classes: int):
    """(h_out, w_out, c_in, c_out, k) of every conv and linear layer, in
    the order they run."""
    out, s = [], image_size
    if kind in RESNET:
        blocks, widths = RESNET[kind]
        out.append((s, s, in_ch, widths[0], 3))
        c = widths[0]
        for st, w in enumerate(widths):
            for b in range(blocks[st]):
                stride = 2 if (b == 0 and st > 0) else 1
                so = -(-s // stride)
                out.append((so, so, c, w, 3))
                out.append((so, so, w, w, 3))
                if stride != 1 or c != w:
                    out.append((so, so, c, w, 1))
                s, c = so, w
        out.append((1, 1, c, num_classes, 1))
        return out
    c = in_ch
    for w in CONV_STACK[kind]:
        out.append((s, s, c, w, 3))
        c = w
        if s > 1:
            s //= 2
    out.append((1, 1, c * s * s, num_classes, 1))
    return out


def generator_layers(*, nz: int, image_size: int, out_ch: int):
    s0, b = image_size // 4, GEN_BASE
    return [(1, 1, nz, 2 * b * s0 * s0, 1),
            (2 * s0, 2 * s0, 2 * b, 2 * b, 3),
            (image_size, image_size, 2 * b, b, 3),
            (image_size, image_size, b, out_ch, 3)]


def pass_flops(ls, n, *, fwd=True, dx=False, dx_first=False, dw=False):
    """Operations of the passes named over the layers ``ls`` on a batch
    of ``n``; ``dx_first`` counts the first layer's input gradient too."""
    per = [conv_flops(n, *layer) for layer in ls]
    tot = sum(per) if fwd else 0
    if dx:
        tot += sum(per) if dx_first else sum(per[1:])
    if dw:
        tot += sum(per)
    return tot


def model_shape(cfg: dict) -> dict:
    return dict(image_size=cfg["image_size"], in_ch=cfg["in_ch"],
                num_classes=cfg["num_classes"])


def stage2_epoch_flops(cfg: dict) -> int:
    """One Algorithm-1 epoch. Each of the T_G generator steps: the
    generator forward and its weight gradient and input gradients (z
    needs none); every client and the student forward and input gradient
    (the loss reaches the generator through the image). Then the student
    step: the generator forward, every client forward, and the student's
    forward and both gradients (the image needs none)."""
    n = cfg["synth_batch"]
    gen = generator_layers(nz=cfg["nz"], image_size=cfg["image_size"],
                           out_ch=cfg["in_ch"])
    shape = model_shape(cfg)
    clients = [layers(k, **shape) for k in cfg["client_kinds"]]
    student = layers(cfg["global_kind"], **shape)
    g_step = (pass_flops(gen, n, dx=True, dw=True)
              + sum(pass_flops(c, n, dx=True, dx_first=True)
                    for c in clients)
              + pass_flops(student, n, dx=True, dx_first=True))
    s_step = (pass_flops(gen, n) + sum(pass_flops(c, n) for c in clients)
              + pass_flops(student, n, dx=True, dw=True))
    return cfg["t_g"] * g_step + s_step


def distill_kl_cost(rows: int, vocab: int, *, backward: bool,
                    teacher_grad: bool = True) -> tuple[int, int]:
    """(operations, HBM bytes) of one call of the KL kernel pair on
    (rows, vocab) float32 logits.

    Forward: per element two running maxima, two exponentials with
    their shifts and sums, and p*(t - s) accumulated (11 operations);
    it reads both logit blocks and writes six per-row statistics.
    Backward: per element two exponentials with shifts, and g*(q - p)
    (6 operations), plus g*p*((t - lt) - (s - ls) - kl) for the teacher
    gradient (6 more); it reads both logit blocks and four per-row
    statistics and writes one or two gradient blocks."""
    elems = rows * vocab
    if not backward:
        return 11 * elems, 2 * elems * F32 + 6 * rows * F32
    ops = (12 if teacher_grad else 6) * elems
    writes = (2 if teacher_grad else 1) * elems * F32
    return ops, 2 * elems * F32 + 4 * rows * F32 + writes
