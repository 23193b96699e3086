"""Operations and bytes the algorithms need, counted from shapes.

These are the numerators of ``step_mfu`` and ``resize_roofline``.  They are
counted from the configuration's sizes alone, never from the program, so a
change to the program cannot change what its time is held against.
"""
from __future__ import annotations

KERNEL_HW = (11, 5, 3, 3, 3)
CONV_STRIDES = (4, 1, 1, 1, 1)
POOL_AFTER = (0, 1, 4)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def alexnet_layers(model: dict) -> list:
    """``[(name, macs_per_image, n_params)]`` of the one-tower AlexNet with
    SAME padding, ReLU, 2x2/2 max-pools after conv 0, 1 and 4, and three
    fully connected layers."""
    hw, c_in = model["in_hw"], model["channels"]
    layers = []
    for i, (c_out, k, s) in enumerate(zip(model["filters"], KERNEL_HW,
                                          CONV_STRIDES)):
        hw = _ceil_div(hw, s)
        layers.append((f"conv{i}", hw * hw * c_out * k * k * c_in,
                       k * k * c_in * c_out + c_out))
        c_in = c_out
        if i in POOL_AFTER:
            hw //= 2
    dims = [hw * hw * c_in, *model["fc"], model["n_classes"]]
    for i in range(3):
        layers.append((f"fc{i}", dims[i] * dims[i + 1],
                       dims[i] * dims[i + 1] + dims[i + 1]))
    return layers


def alexnet_forward_macs(model: dict) -> int:
    """Multiply-accumulates of one image's forward pass."""
    return sum(m for _, m, _ in alexnet_layers(model))


def alexnet_params(model: dict) -> int:
    return sum(p for _, _, p in alexnet_layers(model))


def alexnet_train_flops(model: dict) -> int:
    """FLOPs of one image's training step: the forward pass, the weight
    gradients, and the input gradients of every layer but the first (the
    images need none).  2 FLOPs per multiply-accumulate; bias, ReLU, pooling,
    softmax and the SGD update are left out (under 1% of the total)."""
    layers = alexnet_layers(model)
    fwd = sum(m for _, m, _ in layers)
    return 2 * (3 * fwd - layers[0][1])


def resize_flops(batch: int, h: int, w: int, c: int, out_h: int,
                 out_w: int) -> int:
    """FLOPs of the separable bilinear resize as two matmuls per channel
    plane: ``(out_h, h) @ (h, w)`` then ``(out_h, w) @ (w, out_w)``."""
    return batch * c * 2 * (out_h * h * w + out_h * w * out_w)


def resize_bytes(batch: int, h: int, w: int, c: int, out_h: int, out_w: int,
                 in_itemsize: int = 1, out_itemsize: int = 4) -> int:
    """Bytes the resize must move at least: every input pixel read once and
    every output pixel written once (the two small interpolation matrices
    are left out)."""
    return batch * c * (h * w * in_itemsize + out_h * out_w * out_itemsize)
