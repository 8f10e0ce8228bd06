"""FLOP and byte counts of the benchmark against hand counts."""

from bench import model
from bench.kernels import dcn_fused_batch as kernel

VGG = model.Net("vgg19", 8, "dcn2", 224, 1000)
SEGNET = model.Net("segnet", 8, "dcn2", 224, 11)


def test_vgg19_8_layers():
    ls = model.layers(VGG)
    assert len(ls) == 16
    deform = [(l.hw, l.c_in, l.c_out) for l in ls if l.deform]
    assert deform == [(28, 256, 512)] + [(28, 512, 512)] * 3 \
        + [(14, 512, 512)] * 4
    assert [i for i, l in enumerate(ls) if l.after == "pool"] == \
        [1, 3, 7, 11, 15]


def test_segnet_8_layers():
    ls = model.layers(SEGNET)
    assert len(ls) == 32
    deform = [(l.hw, l.c_in, l.c_out) for l in ls if l.deform]
    assert deform == [(28, 256, 256), (56, 256, 256), (56, 256, 256),
                      (56, 256, 128), (56, 128, 128), (112, 128, 64),
                      (112, 64, 64), (224, 64, 64)]
    assert [i for i, l in enumerate(ls) if l.after == "upsample"] == \
        [16, 20, 24, 28, 30]
    # 8x8 output tiles of the deformable layers: 16 + 4*49 + 2*196 + 784
    assert sum((-(-l.hw // 8)) ** 2 for l in ls if l.deform) == 1388


def test_kernel_work_vgg19_layer():
    # Layer 8: 28x28, 256 -> 512, one image.
    px, kk, ci, co = 28 * 28, 9, 256, 512
    bli = px * kk * ci * 4 * 2          # 4 MACs per sample and channel
    conv = px * kk * ci * co * 2
    assert kernel.flops(28, ci, co, 3, 1) == bli + conv == 1_864_138_752
    planes = px * (ci + co) * 4
    weights = (kk * ci * co + co) * 4
    packed = px * kk * 4 * 8            # int32 index + f32 coefficient
    assert kernel.bytes_moved(28, ci, co, 3, 1) == \
        planes + weights + packed == 7_354_880


def test_kernel_work_segnet_layer():
    # Layer 31: 224x224, 64 -> 64, a step of 8 images.
    px, kk, c = 224 * 224 * 8, 9, 64
    assert kernel.flops(224, c, c, 3, 8) == \
        px * kk * c * 8 + px * kk * c * c * 2 == 31_444_697_088
    assert kernel.bytes_moved(224, c, c, 3, 8) == \
        px * 2 * c * 4 + (kk * c * c + c) * 4 + px * kk * 32 == 321_274_112


def test_kernel_calls_one_per_deformable_layer_per_step():
    calls = kernel.calls(model.layers(SEGNET), [8, 8])
    assert len(calls) == 16
    assert calls[-1] == (kernel.flops(224, 64, 64, 3, 8),
                         kernel.bytes_moved(224, 64, 64, 3, 8))


def test_forward_flops_by_hand():
    def conv(hw, ci, co):
        return 2 * hw * hw * 9 * ci * co

    def extra(hw, ci):                  # offset conv (18 ch) + BLI
        return 2 * hw * hw * 9 * ci * 18 + 8 * hw * hw * 9 * ci

    vgg = (conv(224, 3, 64) + conv(224, 64, 64) + conv(112, 64, 128)
           + conv(112, 128, 128) + conv(56, 128, 256)
           + 3 * conv(56, 256, 256) + conv(28, 256, 512)
           + 3 * conv(28, 512, 512) + 4 * conv(14, 512, 512)
           + extra(28, 256) + 3 * extra(28, 512) + 4 * extra(14, 512)
           + 2 * 512 * 1000)
    assert model.forward_flops(VGG) == vgg
    assert abs(vgg / 1e9 - 39.73) < 0.01
    seg = model.forward_flops(SEGNET)
    assert abs(seg / 1e9 - 73.26) < 0.01
