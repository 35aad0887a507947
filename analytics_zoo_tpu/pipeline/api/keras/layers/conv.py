"""Convolution layers.

Reference surface: `Z/pipeline/api/keras/layers/{Convolution1D,Convolution2D,
Convolution3D,AtrousConvolution2D,SeparableConvolution2D,Deconvolution2D,
Cropping1D,Cropping2D,ZeroPadding1D,ZeroPadding2D,UpSampling1D,UpSampling2D,
UpSampling3D}.scala`.

TPU-first divergence: default data layout is channels-last (NHWC) — the
native TPU conv layout — instead of the reference's theano-style "th"
(NCHW) default. `dim_ordering="th"` is still accepted and handled by
transposing the lax conv dimension-numbers, not the data.
All convs lower to `lax.conv_general_dilated`, which XLA maps onto the MXU.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops import (activations, initializers,
                                   regularizers)
from analytics_zoo_tpu.pipeline.api.keras.engine import KerasLayer, Shape


def _norm_tuple(v, n, name):
    if isinstance(v, int):
        return (v,) * n
    v = tuple(int(x) for x in v)
    if len(v) != n:
        raise ValueError(f"{name} must have length {n}, got {v}")
    return v


def _conv_out_len(length, k, stride, border_mode, dilation=1):
    eff_k = (k - 1) * dilation + 1
    if border_mode == "same":
        return -(-length // stride)
    return -(-(length - eff_k + 1) // stride)


class _ConvND(KerasLayer):
    """Shared N-dim conv implementation (N = 1, 2, 3)."""

    ndim = 2  # spatial dims

    def __init__(self, nb_filter: int, kernel_size, init="glorot_uniform",
                 activation=None, border_mode: str = "valid",
                 subsample=1, dilation=1, dim_ordering: str = "tf",
                 w_regularizer=None, b_regularizer=None, bias: bool = True,
                 groups: int = 1, input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        if border_mode not in ("valid", "same"):
            raise ValueError(f"border_mode must be valid|same, "
                             f"got {border_mode}")
        self.groups = int(groups)
        if self.groups < 1 or int(nb_filter) % self.groups:
            raise ValueError(
                f"nb_filter {nb_filter} must divide by groups "
                f"{groups}")
        if dim_ordering not in ("tf", "th"):
            raise ValueError("dim_ordering must be 'tf' (channels-last) or "
                             "'th' (channels-first)")
        n = self.ndim
        self.nb_filter = int(nb_filter)
        self.kernel_size = _norm_tuple(kernel_size, n, "kernel_size")
        self.subsample = _norm_tuple(subsample, n, "subsample")
        self.dilation = _norm_tuple(dilation, n, "dilation")
        self.border_mode = border_mode
        self.dim_ordering = dim_ordering
        self.kernel_init = initializers.get(init)
        self.activation = activations.get(activation)
        self.w_regularizer = regularizers.get(w_regularizer)
        self.b_regularizer = regularizers.get(b_regularizer)
        self.bias = bias

    # dimension numbers for lax (batch included at runtime)
    def _dn(self):
        n = self.ndim
        sp = "DHW"[3 - n:]
        if self.dim_ordering == "tf":
            io = ("N" + sp + "C", sp + "IO", "N" + sp + "C")
        else:
            io = ("NC" + sp, sp + "IO", "NC" + sp)
        return jax.lax.conv_dimension_numbers(
            (1,) * (n + 2), (1,) * (n + 2), io)

    def _in_channels(self, input_shape: Shape) -> int:
        return (input_shape[-1] if self.dim_ordering == "tf"
                else input_shape[0])

    def build(self, rng, input_shape: Shape) -> dict:
        in_ch = self._in_channels(input_shape)
        if in_ch % self.groups:
            raise ValueError(
                f"input channels {in_ch} must divide by groups "
                f"{self.groups}")
        k_key, _ = jax.random.split(rng)
        w_shape = self.kernel_size + (in_ch // self.groups,
                                      self.nb_filter)
        params = {"kernel": self.kernel_init(k_key, w_shape)}
        if self.bias:
            params["bias"] = jnp.zeros((self.nb_filter,), jnp.float32)
        return params

    @jax.named_scope("zoo:conv/convolve")
    def _convolve(self, x, kernel):
        return jax.lax.conv_general_dilated(
            x, kernel.astype(x.dtype),
            window_strides=self.subsample,
            padding=self.border_mode.upper(),
            rhs_dilation=self.dilation,
            feature_group_count=self.groups,
            dimension_numbers=self._dn())

    def call(self, params, x, *, training=False, rng=None):
        y = self._convolve(x, params["kernel"])
        if self.bias:
            b = params["bias"].astype(y.dtype)
            if self.dim_ordering == "tf":
                y = y + b
            else:
                y = y + b.reshape((1, -1) + (1,) * self.ndim)
        if self.activation is not None:
            y = self.activation(y)
        return y

    def compute_output_shape(self, input_shape: Shape) -> Shape:
        n = self.ndim
        if self.dim_ordering == "tf":
            spatial = input_shape[:n]
        else:
            spatial = input_shape[1:1 + n]
        out_sp = tuple(
            _conv_out_len(s, k, st, self.border_mode, d)
            for s, k, st, d in zip(spatial, self.kernel_size,
                                   self.subsample, self.dilation))
        if self.dim_ordering == "tf":
            return out_sp + (self.nb_filter,)
        return (self.nb_filter,) + out_sp

    def regularizers(self):
        out = []
        if self.w_regularizer is not None:
            out.append(("kernel", self.w_regularizer))
        if self.b_regularizer is not None:
            out.append(("bias", self.b_regularizer))
        return out


class Convolution1D(_ConvND):
    """1D conv over (steps, input_dim) (reference
    `layers/Convolution1D.scala`)."""

    ndim = 1

    def __init__(self, nb_filter: int, filter_length: int, **kwargs):
        kwargs.setdefault("subsample", kwargs.pop("subsample_length", 1))
        super().__init__(nb_filter, filter_length, **kwargs)


class Convolution2D(_ConvND):
    """2D conv (reference `layers/Convolution2D.scala`)."""

    ndim = 2

    def __init__(self, nb_filter: int, nb_row: int, nb_col: Optional[int] =
                 None, **kwargs):
        if nb_col is None:
            kernel = nb_row
        else:
            kernel = (nb_row, nb_col)
        super().__init__(nb_filter, kernel, **kwargs)


class Convolution3D(_ConvND):
    """3D conv (reference `layers/Convolution3D.scala`)."""

    ndim = 3

    def __init__(self, nb_filter: int, kernel_dim1: int,
                 kernel_dim2: Optional[int] = None,
                 kernel_dim3: Optional[int] = None, **kwargs):
        if kernel_dim2 is None:
            kernel = kernel_dim1
        else:
            kernel = (kernel_dim1, kernel_dim2, kernel_dim3)
        super().__init__(nb_filter, kernel, **kwargs)


class AtrousConvolution2D(Convolution2D):
    """Dilated 2D conv (reference `layers/AtrousConvolution2D.scala`)."""

    def __init__(self, nb_filter, nb_row, nb_col=None, atrous_rate=(1, 1),
                 **kwargs):
        kwargs["dilation"] = atrous_rate
        super().__init__(nb_filter, nb_row, nb_col, **kwargs)


class DepthwiseConvolution2D(KerasLayer):
    """Depthwise 2D conv (MobileNet building block; the reference reaches
    it through BigDL's `SpatialSeparableConvolution` used by
    `SeparableConvolution2D.scala`). Implemented with
    ``feature_group_count=in_channels`` so XLA lowers it to a grouped conv
    on the MXU."""

    def __init__(self, nb_row: int, nb_col=None, init="glorot_uniform",
                 activation=None, border_mode="valid", subsample=(1, 1),
                 depth_multiplier=1, dim_ordering="tf", w_regularizer=None,
                 b_regularizer=None, bias=True, input_shape=None, name=None,
                 **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        if border_mode not in ("valid", "same"):
            raise ValueError(f"border_mode must be valid|same, "
                             f"got {border_mode}")
        if dim_ordering not in ("tf", "th"):
            raise ValueError("dim_ordering must be 'tf' or 'th'")
        self.kernel_size = (_norm_tuple(nb_row, 1, "nb_row")[0],
                            _norm_tuple(nb_col if nb_col is not None
                                        else nb_row, 1, "nb_col")[0])
        self.subsample = _norm_tuple(subsample, 2, "subsample")
        self.depth_multiplier = int(depth_multiplier)
        self.border_mode = border_mode
        self.dim_ordering = dim_ordering
        self.kernel_init = initializers.get(init)
        self.activation = activations.get(activation)
        self.w_regularizer = regularizers.get(w_regularizer)
        self.b_regularizer = regularizers.get(b_regularizer)
        self.bias = bias

    def _in_channels(self, input_shape):
        return (input_shape[-1] if self.dim_ordering == "tf"
                else input_shape[0])

    def _out_channels(self, in_ch):
        return in_ch * self.depth_multiplier

    def _dn(self):
        io = (("NHWC", "HWIO", "NHWC") if self.dim_ordering == "tf"
              else ("NCHW", "HWIO", "NCHW"))
        return jax.lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1), io)

    def _depthwise(self, x, params):
        """The shared grouped-conv stage."""
        in_ch = self._in_channels(tuple(x.shape[1:]))
        return jax.lax.conv_general_dilated(
            x, params["depthwise"].astype(x.dtype),
            window_strides=self.subsample,
            padding=self.border_mode.upper(),
            feature_group_count=in_ch,
            dimension_numbers=self._dn())

    def _bias_act(self, y, params):
        if self.bias:
            b = params["bias"].astype(y.dtype)
            y = y + (b if self.dim_ordering == "tf"
                     else b.reshape((1, -1, 1, 1)))
        if self.activation is not None:
            y = self.activation(y)
        return y

    def build(self, rng, input_shape):
        in_ch = self._in_channels(input_shape)
        k1, _ = jax.random.split(rng)
        out_ch = self._out_channels(in_ch)
        params = {"depthwise": self.kernel_init(
            k1, self.kernel_size + (1, in_ch * self.depth_multiplier))}
        if self.bias:
            params["bias"] = jnp.zeros((out_ch,), jnp.float32)
        return params

    def call(self, params, x, *, training=False, rng=None):
        return self._bias_act(self._depthwise(x, params), params)

    def compute_output_shape(self, input_shape):
        out_ch = self._out_channels(self._in_channels(input_shape))
        spatial = (input_shape[:2] if self.dim_ordering == "tf"
                   else input_shape[1:3])
        out_sp = tuple(_conv_out_len(s, k, st, self.border_mode)
                       for s, k, st in zip(spatial, self.kernel_size,
                                           self.subsample))
        if self.dim_ordering == "tf":
            return out_sp + (out_ch,)
        return (out_ch,) + out_sp

    def regularizers(self):
        out = []
        if self.w_regularizer is not None:
            out.append(("depthwise", self.w_regularizer))
        if self.b_regularizer is not None:
            out.append(("bias", self.b_regularizer))
        return out


class SeparableConvolution2D(DepthwiseConvolution2D):
    """Depthwise-separable 2D conv (reference
    `layers/SeparableConvolution2D.scala`): the depthwise stage of
    `DepthwiseConvolution2D` followed by a 1x1 pointwise conv — both
    MXU-friendly."""

    def __init__(self, nb_filter: int, nb_row: int, nb_col=None, **kwargs):
        super().__init__(nb_row, nb_col, **kwargs)
        self.nb_filter = int(nb_filter)

    def _out_channels(self, in_ch):
        return self.nb_filter

    def build(self, rng, input_shape):
        in_ch = self._in_channels(input_shape)
        k1, k2 = jax.random.split(rng)
        params = {
            "depthwise": self.kernel_init(
                k1, self.kernel_size + (1, in_ch * self.depth_multiplier)),
            "pointwise": self.kernel_init(
                k2, (1, 1, in_ch * self.depth_multiplier, self.nb_filter)),
        }
        if self.bias:
            params["bias"] = jnp.zeros((self.nb_filter,), jnp.float32)
        return params

    def call(self, params, x, *, training=False, rng=None):
        y = self._depthwise(x, params)
        y = jax.lax.conv_general_dilated(
            y, params["pointwise"].astype(y.dtype),
            window_strides=(1, 1), padding="VALID",
            dimension_numbers=self._dn())
        return self._bias_act(y, params)

    def regularizers(self):
        out = super().regularizers()
        if self.w_regularizer is not None:
            out.insert(1, ("pointwise", self.w_regularizer))
        return out


class Deconvolution2D(KerasLayer):
    """Transposed 2D conv (reference `layers/Deconvolution2D.scala`)."""

    def __init__(self, nb_filter: int, nb_row: int, nb_col=None,
                 init="glorot_uniform", activation=None,
                 border_mode="valid", subsample=(1, 1), dim_ordering="tf",
                 w_regularizer=None, b_regularizer=None, bias=True,
                 input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.nb_filter = int(nb_filter)
        self.kernel_size = (int(nb_row),
                            int(nb_col if nb_col is not None else nb_row))
        self.subsample = _norm_tuple(subsample, 2, "subsample")
        self.border_mode = border_mode
        self.dim_ordering = dim_ordering
        self.kernel_init = initializers.get(init)
        self.activation = activations.get(activation)
        self.w_regularizer = regularizers.get(w_regularizer)
        self.b_regularizer = regularizers.get(b_regularizer)
        self.bias = bias

    def _in_channels(self, input_shape):
        return (input_shape[-1] if self.dim_ordering == "tf"
                else input_shape[0])

    def build(self, rng, input_shape):
        in_ch = self._in_channels(input_shape)
        k_key, _ = jax.random.split(rng)
        # kernel layout (H, W, out, in) + transpose_kernel=True matches the
        # gradient-of-conv semantics of Keras/torch deconvolution
        params = {"kernel": self.kernel_init(
            k_key, self.kernel_size + (self.nb_filter, in_ch))}
        if self.bias:
            params["bias"] = jnp.zeros((self.nb_filter,), jnp.float32)
        return params

    def call(self, params, x, *, training=False, rng=None):
        io = (("NHWC", "HWIO", "NHWC") if self.dim_ordering == "tf"
              else ("NCHW", "HWIO", "NCHW"))
        y = jax.lax.conv_transpose(
            x, params["kernel"].astype(x.dtype),
            strides=self.subsample,
            padding=self.border_mode.upper(),
            dimension_numbers=io,
            transpose_kernel=True)
        if self.bias:
            b = params["bias"].astype(y.dtype)
            y = y + (b if self.dim_ordering == "tf"
                     else b.reshape((1, -1, 1, 1)))
        if self.activation is not None:
            y = self.activation(y)
        return y

    def compute_output_shape(self, input_shape):
        spatial = (input_shape[:2] if self.dim_ordering == "tf"
                   else input_shape[1:3])
        if self.border_mode == "same":
            out_sp = tuple(s * st for s, st in zip(spatial, self.subsample))
        else:
            out_sp = tuple(s * st + max(k - st, 0)
                           for s, st, k in zip(spatial, self.subsample,
                                               self.kernel_size))
        if self.dim_ordering == "tf":
            return out_sp + (self.nb_filter,)
        return (self.nb_filter,) + out_sp


class ZeroPadding1D(KerasLayer):
    """(reference `layers/ZeroPadding1D.scala`)"""

    def __init__(self, padding=1, input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.padding = _norm_tuple(padding, 2, "padding") \
            if not isinstance(padding, int) else (padding, padding)

    def call(self, params, x, *, training=False, rng=None):
        return jnp.pad(x, ((0, 0), self.padding, (0, 0)))

    def compute_output_shape(self, input_shape):
        return (input_shape[0] + sum(self.padding),) + tuple(input_shape[1:])


class ZeroPadding2D(KerasLayer):
    """(reference `layers/ZeroPadding2D.scala`)

    ``value`` (default 0) sets the pad constant — e.g. ``-inf`` when a
    torch padded MaxPool2d is imported, whose implicit padding must
    never win the max (torch pads with -inf, not 0)."""

    def __init__(self, padding=(1, 1), dim_ordering="tf", input_shape=None,
                 name=None, value=0.0, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        if (isinstance(padding, (tuple, list)) and len(padding) == 2
                and all(isinstance(q, (tuple, list)) and len(q) == 2
                        for q in padding)):
            # keras-2 style asymmetric form ((top, bottom), (l, r))
            self.padding = (tuple(int(v) for v in padding[0]),
                            tuple(int(v) for v in padding[1]))
        else:
            p = _norm_tuple(padding, 2, "padding")
            self.padding = ((p[0], p[0]), (p[1], p[1]))
        self.dim_ordering = dim_ordering
        self.value = value

    def call(self, params, x, *, training=False, rng=None):
        if self.dim_ordering == "tf":
            pads = ((0, 0),) + self.padding + ((0, 0),)
        else:
            pads = ((0, 0), (0, 0)) + self.padding
        val = self.value
        if val == float("-inf"):  # representable floor for the dtype
            val = jnp.finfo(x.dtype).min if jnp.issubdtype(
                x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return jnp.pad(x, pads, constant_values=val)

    def compute_output_shape(self, input_shape):
        s = list(input_shape)
        if self.dim_ordering == "tf":
            s[0] += sum(self.padding[0])
            s[1] += sum(self.padding[1])
        else:
            s[1] += sum(self.padding[0])
            s[2] += sum(self.padding[1])
        return tuple(s)


class Cropping1D(KerasLayer):
    """(reference `layers/Cropping1D.scala`)"""

    def __init__(self, cropping=(1, 1), input_shape=None, name=None,
                 **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.cropping = _norm_tuple(cropping, 2, "cropping")

    def call(self, params, x, *, training=False, rng=None):
        a, b = self.cropping
        return x[:, a:x.shape[1] - b, :]

    def compute_output_shape(self, input_shape):
        return (input_shape[0] - sum(self.cropping),) + \
            tuple(input_shape[1:])


class Cropping2D(KerasLayer):
    """(reference `layers/Cropping2D.scala`)"""

    def __init__(self, cropping=((0, 0), (0, 0)), dim_ordering="tf",
                 input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        if isinstance(cropping, int):
            cropping = ((cropping, cropping), (cropping, cropping))
        self.cropping = tuple(tuple(int(v) for v in c) for c in cropping)
        self.dim_ordering = dim_ordering

    def call(self, params, x, *, training=False, rng=None):
        (t, b), (l, r) = self.cropping
        if self.dim_ordering == "tf":
            return x[:, t:x.shape[1] - b, l:x.shape[2] - r, :]
        return x[:, :, t:x.shape[2] - b, l:x.shape[3] - r]

    def compute_output_shape(self, input_shape):
        s = list(input_shape)
        (t, b), (l, r) = self.cropping
        if self.dim_ordering == "tf":
            s[0] -= t + b
            s[1] -= l + r
        else:
            s[1] -= t + b
            s[2] -= l + r
        return tuple(s)


class UpSampling1D(KerasLayer):
    """(reference `layers/UpSampling1D.scala`)"""

    def __init__(self, length=2, input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.length = int(length)

    def call(self, params, x, *, training=False, rng=None):
        return jnp.repeat(x, self.length, axis=1)

    def compute_output_shape(self, input_shape):
        return (input_shape[0] * self.length,) + tuple(input_shape[1:])


class UpSampling2D(KerasLayer):
    """(reference `layers/UpSampling2D.scala`)"""

    def __init__(self, size=(2, 2), dim_ordering="tf", input_shape=None,
                 name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.size = _norm_tuple(size, 2, "size")
        self.dim_ordering = dim_ordering

    def call(self, params, x, *, training=False, rng=None):
        if self.dim_ordering == "tf":
            y = jnp.repeat(x, self.size[0], axis=1)
            return jnp.repeat(y, self.size[1], axis=2)
        y = jnp.repeat(x, self.size[0], axis=2)
        return jnp.repeat(y, self.size[1], axis=3)

    def compute_output_shape(self, input_shape):
        s = list(input_shape)
        if self.dim_ordering == "tf":
            s[0] *= self.size[0]
            s[1] *= self.size[1]
        else:
            s[1] *= self.size[0]
            s[2] *= self.size[1]
        return tuple(s)


class UpSampling3D(KerasLayer):
    """(reference `layers/UpSampling3D.scala`)"""

    def __init__(self, size=(2, 2, 2), input_shape=None, name=None,
                 **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.size = _norm_tuple(size, 3, "size")

    def call(self, params, x, *, training=False, rng=None):
        y = x
        for i, s in enumerate(self.size):
            y = jnp.repeat(y, s, axis=i + 1)
        return y

    def compute_output_shape(self, input_shape):
        s = list(input_shape)
        for i in range(3):
            s[i] *= self.size[i]
        return tuple(s)


# Keras-2-style aliases (reference keras2 layer set, SURVEY.md §2.4)
Conv1D = Convolution1D
Conv2D = Convolution2D
Conv3D = Convolution3D
Conv2DTranspose = Deconvolution2D
SeparableConv2D = SeparableConvolution2D
