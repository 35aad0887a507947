"""Plain float32 ResNet (bottleneck, v1.5) with its loss, gradients
and SGD-with-momentum steps: the reference the train cells' timed
path is held to. Straight ``jax.numpy``/``lax``, every product at
``highest`` precision, no kernels; imports nothing of the program.

Parameters are a flat ``{layer: {leaf: array}}`` dict under the
program's own layer names (``stem``, ``s1b0_c2_bn``, ``fc``, ...), made
by ``benchmark/weights.py``. BatchNorm keeps its moving mean and
variance under ``_state``; those are not differentiated.

``quant`` puts the same model in the next precision down: every
convolution's and the classifier's operands are rounded to float8
(e4m3, scaled to the tensor's largest magnitude, per output channel
for weights) and the products accumulated in float32, as a float8
matmul path would. That is the control a correct bf16 program has to
be told apart from.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8, F8_MAX = jnp.float8_e4m3fn, 448.0


def block_names(cfg: dict):
    """(name, width, stride, has_projection) of every bottleneck."""
    for stage, (n, width) in enumerate(
            zip(cfg["stage_blocks"], cfg["stage_widths"])):
        for b in range(n):
            yield (f"s{stage}b{b}", width,
                   2 if (stage > 0 and b == 0) else 1, b == 0)


def param_shapes(cfg: dict) -> dict:
    """{(layer, ..., leaf): shape} of every weight the model has."""
    shapes = {}

    def conv(name, k, cin, cout):
        shapes[(name, "kernel")] = (k, k, cin, cout)
        for leaf in ("gamma", "beta"):
            shapes[(name + "_bn", leaf)] = (cout,)
        for leaf in ("moving_mean", "moving_var"):
            shapes[(name + "_bn", "_state", leaf)] = (cout,)

    stem = cfg["stem"]
    conv("stem", stem["kernel"], cfg["in_channels"], stem["filters"])
    cin, exp = stem["filters"], cfg["expansion"]
    for name, width, _stride, proj in block_names(cfg):
        conv(name + "_c1", 1, cin, width)
        conv(name + "_c2", 3, width, width)
        conv(name + "_c3", 1, width, width * exp)
        if proj:
            conv(name + "_down", 1, cin, width * exp)
        cin = width * exp
    shapes[("fc", "kernel")] = (cin, cfg["num_classes"])
    shapes[("fc", "bias")] = (cfg["num_classes"],)
    return shapes


def nest(flat: dict) -> dict:
    """{(a, b, c): v} -> {a: {b: {c: v}}}."""
    out: dict = {}
    for path, v in flat.items():
        d = out
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = v
    return out


def flatten(tree: dict, prefix: tuple = ()) -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out


def _fake_f8(x, axes):
    """Round to float8 at a scale of the largest magnitude over
    ``axes``; gradients pass straight through."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(F8).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def _conv(x, w, stride, quant):
    if quant:
        x = _fake_f8(x, None)
        w = _fake_f8(w, (0, 1, 2))
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _bn(p, x, cfg, new_state, name):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    m = cfg["bn_momentum"]
    st = p["_state"]
    new_state[(name, "_state", "moving_mean")] = \
        m * st["moving_mean"] + (1 - m) * lax.stop_gradient(mean)
    new_state[(name, "_state", "moving_var")] = \
        m * st["moving_var"] + (1 - m) * lax.stop_gradient(var)
    return (x - mean) * lax.rsqrt(var + cfg["bn_epsilon"]) * \
        p["gamma"] + p["beta"]


def _conv_bn(params, h, name, stride, cfg, quant, new_state,
             relu=True):
    h = _conv(h, params[name]["kernel"], stride, quant)
    h = _bn(params[name + "_bn"], h, cfg, new_state, name + "_bn")
    return jax.nn.relu(h) if relu else h


def _bottleneck(params, h, name, stride, proj, cfg, quant):
    """1x1 reduce, 3x3 (carrying the stride), 1x1 expand, shortcut."""
    new_state: dict = {}
    args = (cfg, quant, new_state)
    y = _conv_bn(params, h, name + "_c1", 1, *args)
    y = _conv_bn(params, y, name + "_c2", stride, *args)
    y = _conv_bn(params, y, name + "_c3", 1, *args, relu=False)
    if proj:
        h = _conv_bn(params, h, name + "_down", stride, *args,
                     relu=False)
    return jax.nn.relu(y + h), new_state


def forward(params: dict, x, cfg: dict, quant: bool = False):
    """Training-mode forward: (logits, {state path: new value}).
    Each bottleneck is rematerialised in the backward pass, so that
    float32 activations of a whole batch fit beside the weights."""
    new_state: dict = {}
    stem = cfg["stem"]
    h = _conv_bn(params, x, "stem", stem["stride"], cfg, quant,
                 new_state)
    k, s = stem["pool_kernel"], stem["pool_stride"]
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, k, k, 1),
                          (1, s, s, 1), "SAME")
    for name, _width, stride, proj in block_names(cfg):
        mine = {k: v for k, v in params.items()
                if k.startswith(name + "_")}
        h, st = jax.checkpoint(
            lambda p, a, name=name, stride=stride, proj=proj:
            _bottleneck(p, a, name, stride, proj, cfg, quant))(mine, h)
        new_state.update(st)
    h = jnp.mean(h, axis=(1, 2))
    w = params["fc"]["kernel"]
    if quant:
        h, w = _fake_f8(h, None), _fake_f8(w, (0,))
    logits = jnp.dot(h, w, precision=HIGHEST) + params["fc"]["bias"]
    return logits, new_state


def loss_and_state(params: dict, x, y, cfg: dict, quant: bool):
    logits, new_state = forward(params, x, cfg, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, y.reshape(-1, 1), axis=-1)
    return -jnp.mean(picked), new_state


def make_step(cfg: dict, quant: bool = False):
    """One jitted SGD-with-momentum step on flat dicts:
    (weights, state, velocity, x, y, lr) ->
    (weights', state', velocity', loss, gradients). The rate is an
    argument, so that the calibration's frozen step (rate nought) is
    the same compiled program."""
    mom = cfg["optimizer"]["momentum"]

    def step(weights, state, velocity, x, y, lr):
        def f(w):
            return loss_and_state(nest({**w, **state}), x, y, cfg,
                                  quant)
        (loss, new_state), grads = jax.value_and_grad(
            f, has_aux=True)(weights)
        velocity = {k: mom * velocity[k] + grads[k] for k in grads}
        weights = {k: weights[k] - lr * velocity[k] for k in weights}
        return weights, new_state, velocity, loss, grads

    return jax.jit(step)


def split(flat: dict):
    """(trainable weights, BatchNorm state) of a flat parameter dict."""
    state = {k: v for k, v in flat.items() if "_state" in k}
    return {k: v for k, v in flat.items() if k not in state}, state


def run_steps(cfg: dict, flat_params: dict, batches, quant=False,
              step=None, lr=None):
    """Follow ``batches`` [(x, y), ...] from ``flat_params``. Returns
    the losses, the first step's gradients and the parameters after
    the last step (flat dicts of device arrays)."""
    step = step or make_step(cfg, quant)
    lr = jnp.float32(cfg["optimizer"]["lr"] if lr is None else lr)
    weights, state = split({k: jnp.asarray(v, jnp.float32)
                            for k, v in flat_params.items()})
    velocity = {k: jnp.zeros_like(v) for k, v in weights.items()}
    losses, first_grads = [], None
    for x, y in batches:
        weights, state, velocity, loss, grads = step(
            weights, state, velocity, jnp.asarray(x, jnp.float32),
            jnp.asarray(y, jnp.int32), lr)
        losses.append(float(loss))
        if first_grads is None:
            first_grads = grads
    return losses, first_grads, {**weights, **state}
