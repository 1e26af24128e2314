"""The operation and byte counts the utilization and roofline metrics
divide by, against hand counts."""
from harness import flops

CIFAR = dict(image_size=32, in_ch=3, num_classes=10)


def test_one_conv_layer_by_hand():
    # ResNet-18 stage 1: 3x3, 64 -> 64 channels at 32x32, batch 128
    assert flops.conv_flops(128, 32, 32, 64, 64, 3) == \
        2 * 128 * 32 * 32 * 64 * 64 * 9 == 9_663_676_416
    ls = flops.layers("resnet18", **CIFAR)
    assert ls[1] == (32, 32, 64, 64, 3)
    fwd = flops.pass_flops([ls[1]], 128)
    assert flops.pass_flops([ls[1]], 128, dx=True, dx_first=True,
                            dw=True) == 3 * fwd


def test_resnet18_forward_by_hand():
    # stem + 4 stage-1 convs + 3 stages of (strided c1, c2, 1x1 proj,
    # 2 convs) + the linear head, one 32x32 image
    stem = 2 * 32 * 32 * 3 * 64 * 9
    stage1 = 4 * 2 * 32 * 32 * 64 * 64 * 9
    later = 0
    for s, ci, co in ((16, 64, 128), (8, 128, 256), (4, 256, 512)):
        later += 2 * s * s * (ci * co * 9 + co * co * 9 + ci * co
                              + 2 * co * co * 9)
    head = 2 * 512 * 10
    want = stem + stage1 + later + head
    assert want == 1_110_845_440
    assert flops.pass_flops(flops.layers("resnet18", **CIFAR), 1) == want


def test_stage2_epoch_by_parts():
    cfg = dict(CIFAR, synth_batch=128, nz=100, t_g=30,
               client_kinds=["resnet18"] * 5, global_kind="resnet18")
    n = 128
    net = flops.pass_flops(flops.layers("resnet18", **CIFAR), n)
    first = flops.conv_flops(n, *flops.layers("resnet18", **CIFAR)[0])
    gen_ls = flops.generator_layers(nz=100, image_size=32, out_ch=3)
    gen = flops.pass_flops(gen_ls, n)
    gen_first = flops.conv_flops(n, *gen_ls[0])
    g_step = (3 * gen - gen_first) + 6 * 2 * net
    s_step = gen + 5 * net + (3 * net - first)
    assert flops.stage2_epoch_flops(cfg) == 30 * g_step + s_step
    # about 55 TFLOP an epoch at the paper's shapes
    assert 5.4e13 < flops.stage2_epoch_flops(cfg) < 5.6e13


def test_distill_kl_call_by_hand():
    # one 128 x 10 float32 call: both logit blocks in, six row stats out
    ops, nbytes = flops.distill_kl_cost(128, 10, backward=False)
    assert (ops, nbytes) == (11 * 1280, 2 * 1280 * 4 + 6 * 128 * 4)
    ops, nbytes = flops.distill_kl_cost(128, 10, backward=True)
    assert (ops, nbytes) == (12 * 1280,
                             2 * 1280 * 4 + 4 * 128 * 4 + 2 * 1280 * 4)
    ops, nbytes = flops.distill_kl_cost(128, 10, backward=True,
                                        teacher_grad=False)
    assert (ops, nbytes) == (6 * 1280,
                             2 * 1280 * 4 + 4 * 128 * 4 + 1280 * 4)
    # memory-bound on a v5e: bytes / 819 GB/s outweighs ops / 197 TFLOP/s
    assert nbytes / 819e9 > ops / 197e12
