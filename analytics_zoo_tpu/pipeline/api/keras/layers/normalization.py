"""Normalization layers.

Reference surface: `Z/pipeline/api/keras/layers/BatchNormalization.scala`
(+ the internal LayerNorm used by `TransformerLayer.scala`/`BERT.scala`).

BatchNormalization is the one stateful layer in the framework: moving
mean/var live in ``params["_state"]`` and training-mode forward returns
their update through ``apply``'s second result (see engine.py contract).
Under pjit the batch statistics are computed over the *global* batch —
XLA inserts the cross-device all-reduce for the mean/var automatically
because the reduction crosses the sharded batch axis. This replaces the
reference's per-replica local statistics (BigDL replicas each normalize
their slice), and is strictly more accurate (syncBN semantics).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.pipeline.api.keras.engine import KerasLayer, Shape


def bn_batch_stats(ssum, ssq, count, state, momentum):
    """Batch mean/var from moving-mean-SHIFTED sums ``Σ(x−mm)`` /
    ``Σ(x−mm)²`` plus the moving-average update, for
    :class:`BatchNormalization`. The shift keeps
    E[x²]−E[x]² from cancelling when |mean| ≫ std; the moving mean is
    stop-gradded (it is frozen state, not a differentiable input)."""
    mm = jax.lax.stop_gradient(state["moving_mean"])
    d_mean = ssum / count
    d_sq = ssq / count
    mean = d_mean + mm
    var = jnp.maximum(d_sq - jnp.square(d_mean), 0.0)
    m = momentum
    updates = {"_state": {
        "moving_mean": m * state["moving_mean"] + (1 - m) * mean,
        "moving_var": m * state["moving_var"] + (1 - m) * var,
    }}
    return mean, var, updates


def bn_fold(mean, var, gamma, beta, epsilon):
    """Fold ``(x−mean)·rsqrt(var+eps)·γ+β`` into per-channel
    ``(scale, shift)`` for a single FMA apply (γ/β may be None)."""
    inv = jax.lax.rsqrt(var + epsilon)
    scale = inv * gamma if gamma is not None else inv
    shift = -mean * scale
    if beta is not None:
        shift = shift + beta
    return scale, shift


class BatchNormalization(KerasLayer):
    def __init__(self, epsilon: float = 1e-3, momentum: float = 0.99,
                 beta_init="zero", gamma_init="one", dim_ordering="tf",
                 center: bool = True, scale: bool = True,
                 input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.epsilon = float(epsilon)
        self.momentum = float(momentum)
        self.center = center
        self.scale = scale
        self.dim_ordering = dim_ordering

    def _feature_axis(self, ndim_with_batch: int) -> int:
        # channels-last ("tf") normalizes the trailing axis; "th" axis 1
        return (ndim_with_batch - 1) if self.dim_ordering == "tf" else 1

    def _num_features(self, input_shape: Shape) -> int:
        return (input_shape[-1] if self.dim_ordering == "tf"
                else input_shape[0])

    def build(self, rng, input_shape: Shape) -> dict:
        n = self._num_features(input_shape)
        params = {}
        if self.scale:
            params["gamma"] = jnp.ones((n,), jnp.float32)
        if self.center:
            params["beta"] = jnp.zeros((n,), jnp.float32)
        params["_state"] = {
            "moving_mean": jnp.zeros((n,), jnp.float32),
            "moving_var": jnp.ones((n,), jnp.float32),
        }
        return params

    def _reshape_stat(self, stat, x):
        axis = self._feature_axis(x.ndim)
        shape = [1] * x.ndim
        shape[axis] = stat.shape[0]
        return stat.reshape(shape)

    def apply(self, params, x, *, training=False, rng=None):
        axis = self._feature_axis(x.ndim)
        reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
        state = params["_state"]
        if training:
            # single pass over x: both reductions fuse into one
            # multi-output kernel reading x once (profiling showed BN
            # reductions, not convs, dominate the ResNet-50 step)
            with jax.named_scope("zoo:bn/stats"):
                shift0 = self._reshape_stat(
                    jax.lax.stop_gradient(state["moving_mean"]), x)
                xf = x.astype(jnp.float32) - shift0
                count = float(np.prod([x.shape[a]
                                       for a in reduce_axes]))
                mean, var, updates = bn_batch_stats(
                    jnp.sum(xf, axis=reduce_axes),
                    jnp.sum(jnp.square(xf), axis=reduce_axes),
                    count, state, self.momentum)
        else:
            mean, var = state["moving_mean"], state["moving_var"]
            updates = {}
        # fold (x-mean)*inv*gamma+beta into one per-element FMA: the
        # per-channel scale/shift vectors are computed in f32 off the
        # hot path, so the activation tensor is read once, written once
        with jax.named_scope("zoo:bn/apply"):
            scale, shift = bn_fold(
                mean, var, params["gamma"] if self.scale else None,
                params["beta"] if self.center else None, self.epsilon)
            y = x * self._reshape_stat(scale, x).astype(x.dtype) + \
                self._reshape_stat(shift, x).astype(x.dtype)
        return y, updates

    def call(self, params, x, *, training=False, rng=None):
        y, _ = self.apply(params, x, training=training, rng=rng)
        return y


class LayerNormalization(KerasLayer):
    """LayerNorm over the trailing axis (the internal norm of the
    reference's `TransformerLayer.scala`/`BERT.scala`)."""

    def __init__(self, epsilon: float = 1e-5, center: bool = True,
                 scale: bool = True, input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.epsilon = float(epsilon)
        self.center = center
        self.scale = scale

    def build(self, rng, input_shape: Shape) -> dict:
        n = input_shape[-1]
        params = {}
        if self.scale:
            params["gamma"] = jnp.ones((n,), jnp.float32)
        if self.center:
            params["beta"] = jnp.zeros((n,), jnp.float32)
        return params

    def call(self, params, x, *, training=False, rng=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.epsilon)
        if self.scale:
            y = y * params["gamma"].astype(y.dtype)
        if self.center:
            y = y + params["beta"].astype(y.dtype)
        return y


class WithinChannelLRN2D(KerasLayer):
    """Local response normalization within channels (reference
    `layers/WithinChannelLRN2D.scala`)."""

    def __init__(self, size: int = 5, alpha: float = 1.0,
                 beta: float = 0.75, input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.size = int(size)
        self.alpha = float(alpha)
        self.beta = float(beta)

    def call(self, params, x, *, training=False, rng=None):
        # NHWC: average x^2 over a size×size spatial window
        sq = jnp.square(x)
        window = (1, self.size, self.size, 1)
        summed = jax.lax.reduce_window(
            sq, 0.0, jax.lax.add, window, (1, 1, 1, 1), "SAME")
        counts = jax.lax.reduce_window(
            jnp.ones_like(x), 0.0, jax.lax.add, window, (1, 1, 1, 1),
            "SAME")
        denom = (1.0 + self.alpha * summed / counts) ** self.beta
        return x / denom
