"""ResNet v1.5 for image classification — the nnframes ResNet-50/ImageNet
headline workload (reference recipe `examples/inception/Train.scala`
is the equivalent CNN training recipe).

One graph, all XLA: every block is `_bottleneck` (Convolution2D +
BatchNormalization layers); the Pallas fused conv+BN bottlenecks and
the phase-decomposed strided backward that used to sit beside it ran
slower on the v5e and were deleted (PERF.md §6, PR 32).

TPU-first choices:
- NHWC layout end-to-end (native TPU conv layout).
- Channel counts are multiples of 64/128 → clean MXU tiling.
- BatchNorm statistics are global-batch under pjit (syncBN for free).
- Feed bf16 inputs for MXU throughput; params stay f32 (layers cast).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops import initializers
from analytics_zoo_tpu.pipeline.api.keras.engine import Input, KerasLayer
from analytics_zoo_tpu.pipeline.api.keras.models import Model
from analytics_zoo_tpu.pipeline.api.keras.layers import (
    Activation, BatchNormalization, Convolution2D, Dense,
    GlobalAveragePooling2D, Add, MaxPooling2D,
)


def conv_bn(x, filters, kernel, stride=1, activation="relu",
             name=None):
    x = Convolution2D(filters, kernel, kernel, subsample=stride,
                      border_mode="same", bias=False, name=name)(x)
    x = BatchNormalization(name=None if name is None else name + "_bn")(x)
    if activation:
        x = Activation(activation)(x)
    return x


def _bottleneck(x, filters, stride=1, downsample=False, name=""):
    """v1.5 bottleneck: stride lives on the 3x3 conv."""
    shortcut = x
    y = conv_bn(x, filters, 1, 1, name=name + "_c1")
    y = conv_bn(y, filters, 3, stride, name=name + "_c2")
    y = Convolution2D(filters * 4, 1, 1, border_mode="same", bias=False,
                      name=name + "_c3")(y)
    y = BatchNormalization(name=name + "_c3_bn")(y)
    if downsample:
        shortcut = Convolution2D(filters * 4, 1, 1, subsample=stride,
                                 border_mode="same", bias=False,
                                 name=name + "_down")(x)
        shortcut = BatchNormalization(name=name + "_down_bn")(shortcut)
    out = Add()([y, shortcut])
    return Activation("relu")(out)


class SpaceToDepth2D(KerasLayer):
    """NHWC space-to-depth: (H, W, C) → (H/b, W/b, b²·C), channel
    order (row-offset, col-offset, channel)."""

    def __init__(self, block: int = 2, input_shape=None, name=None,
                 **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.block = int(block)

    def call(self, params, x, *, training=False, rng=None):
        b = self.block
        n, h, w, c = x.shape
        x = x.reshape(n, h // b, b, w // b, b, c)
        x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
        return x.reshape(n, h // b, w // b, b * b * c)

    def compute_output_shape(self, input_shape):
        h, w, c = input_shape
        b = self.block
        if h % b or w % b:
            raise ValueError(f"spatial dims {h}x{w} not divisible by "
                             f"block {b}")
        return (h // b, w // b, b * b * c)


class S2DStemConv(KerasLayer):
    """The MLPerf-style space-to-depth stem: the 7×7/s2 SAME stem conv
    re-expressed as a 4×4/s1 conv over the space-to-depth(2) input with
    asymmetric padding ((1,2),(1,2)) — mathematically the same map
    (see `s2d_stem_kernel` for the exact kernel correspondence), but
    MXU-dense: 12 input channels instead of 3, no strided gather.
    """

    def __init__(self, nb_filter: int = 64, init="glorot_uniform",
                 input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.nb_filter = int(nb_filter)
        self.kernel_init = initializers.get(init)

    def build(self, rng, input_shape):
        in_ch = input_shape[-1]
        return {"kernel": self.kernel_init(
            rng, (4, 4, in_ch, self.nb_filter))}

    def call(self, params, x, *, training=False, rng=None):
        return jax.lax.conv_general_dilated(
            x, params["kernel"].astype(x.dtype),
            window_strides=(1, 1), padding=((1, 2), (1, 2)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def compute_output_shape(self, input_shape):
        h, w, _ = input_shape
        return (h, w, self.nb_filter)


def s2d_stem_kernel(k7: np.ndarray) -> np.ndarray:
    """Exact kernel correspondence: a (7,7,C,F) SAME/s2 stem kernel →
    the (4,4,4C,F) kernel for `S2DStemConv` over `SpaceToDepth2D(2)`
    input producing IDENTICAL outputs. (Derivation: pad 7→8 with a
    zero last row/col so stride 2 tiles the kernel; fold the 2×2
    phases into channels.)"""
    kh, kw, c, f = k7.shape
    assert (kh, kw) == (7, 7)
    k8 = np.zeros((8, 8, c, f), k7.dtype)
    k8[:7, :7] = k7
    # K2d[u', v', (r, s, c)] = K8[2u'+r, 2v'+s, c]
    k8 = k8.reshape(4, 2, 4, 2, c, f)           # (u', r, v', s, c, f)
    k2d = np.transpose(k8, (0, 2, 1, 3, 4, 5))  # (u', v', r, s, c, f)
    return np.ascontiguousarray(k2d.reshape(4, 4, 4 * c, f))


class ResNet:
    """Builder; `ResNet(depth).build(input_shape, classes)` → keras Model."""

    DEPTH_BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3),
                    152: (3, 8, 36, 3)}

    def __init__(self, depth: int = 50):
        if depth not in self.DEPTH_BLOCKS:
            raise ValueError(f"depth must be one of "
                             f"{sorted(self.DEPTH_BLOCKS)}")
        self.depth = depth

    def build(self, input_shape=(224, 224, 3), classes: int = 1000,
              space_to_depth: bool = False) -> Model:
        """Stem (7x7/s2, or its space-to-depth form), 3x3/s2 max
        pool, the stages of `_bottleneck` blocks, global average
        pool and the classifier."""
        blocks = self.DEPTH_BLOCKS[self.depth]
        inp = Input(input_shape, name="image")
        if space_to_depth:
            # MXU-dense stem (see S2DStemConv); identical output map
            x = SpaceToDepth2D(2, name="stem_s2d")(inp)
            x = S2DStemConv(64, name="stem")(x)
            x = BatchNormalization(name="stem_bn")(x)
            x = Activation("relu")(x)
        else:
            x = conv_bn(inp, 64, 7, stride=2, name="stem")
        # stem maxpool backward: mask/count distribution instead of
        # select_and_scatter (ops.pool_grad, ZOO_TPU_MAXPOOL_MASK_BWD)
        x = MaxPooling2D(pool_size=3, strides=2, border_mode="same")(x)
        filters = 64
        for stage, n_blocks in enumerate(blocks):
            first_stride = 2 if stage > 0 else 1
            for b in range(n_blocks):
                stride = first_stride if b == 0 else 1
                x = _bottleneck(x, filters, stride=stride,
                                downsample=(b == 0),
                                name=f"s{stage}b{b}")
            filters *= 2
        x = GlobalAveragePooling2D()(x)
        out = Dense(classes, name="fc")(x)
        return Model(inp, out, name=f"resnet{self.depth}")


def resnet50(input_shape=(224, 224, 3), classes: int = 1000,
             space_to_depth: bool = False) -> Model:
    return ResNet(50).build(input_shape, classes,
                            space_to_depth=space_to_depth)
