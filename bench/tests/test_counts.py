"""The yardstick's counts against hand counts, and the table of peaks; the
ones a run records, through the architecture's ``counts``."""
import pytest

from bench import flops, peaks
from bench.spec import Spec

SPEC = Spec()
CFG = SPEC.config("alexnet_caltech101")
CALTECH = CFG["model"]
# the same network on ImageNet-1k's 1,000 classes
IMAGENET = dict(CALTECH, n_classes=1000)
ALEXNET = SPEC.arch("alexnet")
DEVICE_RESIZE = SPEC.traffic("device_resize")


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
    counts = ALEXNET.counts(CFG, DEVICE_RESIZE, CFG["batch"])
    assert counts["train_flops_per_sample"] == 2 * (3 * fwd - conv0)


def test_resize_counts_for_a_batch_of_32():
    counts = ALEXNET.counts(CFG, DEVICE_RESIZE, 32)["resize"]
    assert counts["bytes"] == 25_559_040
    assert counts["flops"] == 96 * 2 * (224 * 256 * 256 + 224 * 256 * 224)


@pytest.mark.parametrize("preprocess,kernel", [("pallas", True),
                                               ("numpy", False)])
def test_the_resize_kernel_is_counted_where_the_traffic_uses_it(preprocess,
                                                                 kernel):
    traffic = dict(DEVICE_RESIZE, batched_preprocess=preprocess)
    assert ALEXNET.counts(CFG, traffic, 32)["uses_resize_kernel"] is kernel


def test_peaks_of_a_v5e():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_s, p.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "TPU v5e" in p.source


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
