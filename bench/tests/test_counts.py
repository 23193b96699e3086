"""The yardstick's counts against hand counts, and the table of peaks."""
import pytest

from bench import flops, peaks
from bench.spec import Spec

CALTECH = Spec().config("alexnet_caltech101")["model"]
# the same network on ImageNet-1k's 1,000 classes
IMAGENET = dict(CALTECH, n_classes=1000)


def test_alexnet_forward_macs_at_224():
    conv = [56 * 56 * 64 * 11 * 11 * 3, 28 * 28 * 192 * 5 * 5 * 64,
            14 * 14 * 384 * 9 * 192, 14 * 14 * 256 * 9 * 384,
            14 * 14 * 256 * 9 * 256]
    fc = [7 * 7 * 256 * 4096, 4096 * 4096, 4096 * 102]
    assert flops.alexnet_forward_macs(CALTECH) == sum(conv + fc) \
        == 801_345_536


@pytest.mark.parametrize("model,n", [(CALTECH, 71_053_222),
                                     (IMAGENET, 74_732_328)])
def test_alexnet_params(model, n):
    assert flops.alexnet_params(model) == n


def test_train_flops_leave_out_the_first_input_gradient():
    fwd = flops.alexnet_forward_macs(CALTECH)
    conv0 = flops.alexnet_layers(CALTECH)[0][1]
    assert flops.alexnet_train_flops(CALTECH) == 2 * (3 * fwd - conv0)


def test_resize_counts_for_a_batch_of_32():
    assert flops.resize_bytes(32, 256, 256, 3, 224, 224) == 25_559_040
    assert flops.resize_flops(32, 256, 256, 3, 224, 224) == \
        96 * 2 * (224 * 256 * 256 + 224 * 256 * 224)


def test_peaks_of_a_v5e():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_s, p.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "TPU v5e" in p.source


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
