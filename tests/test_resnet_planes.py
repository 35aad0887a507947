"""The one ResNet path held to a reference of its own.

The conv+BatchNorm planes ResNet-50 is made of, each at its
published height, width and channels (batch 2), the strided
convolution's gradients, and the bottleneck block, each against a
plain reference written here: convolution as a sum over kernel taps
of strided slices times a matrix (float32 at ``highest`` precision in
jax for the planes and the block, float64 numpy with hand-written
adjoints for the strided gradients), BatchNorm as mean / biased
variance over N, H, W. Nothing here calls the layers' own helpers
or `lax.conv_general_dilated`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.models.image.imageclassification import resnet
from analytics_zoo_tpu.pipeline.api.keras.engine import Input
from analytics_zoo_tpu.pipeline.api.keras.layers import Convolution2D
from analytics_zoo_tpu.pipeline.api.keras.models import Model

F32, BF16 = jnp.float32, jnp.bfloat16
EPS, MOMENTUM = 1e-3, 0.99          # the builder's BatchNorm defaults
HI = jax.lax.Precision.HIGHEST

# (name, input H x W x C, kernel, filters, stride): every distinct
# convolution + BatchNorm plane of resnet50(), in the builder's order;
# c1 / c2 / stem are followed by a ReLU, c3 / down are not
PLANES = [
    ("stem", (224, 224, 3), 7, 64, 2),
    ("s0b0_c1", (56, 56, 64), 1, 64, 1),
    ("s0_c2", (56, 56, 64), 3, 64, 1),
    ("s0_c3", (56, 56, 64), 1, 256, 1),     # also s0b0_down
    ("s0_c1", (56, 56, 256), 1, 64, 1),
    ("s1b0_c1", (56, 56, 256), 1, 128, 1),
    ("s1b0_c2", (56, 56, 128), 3, 128, 2),
    ("s1_c3", (28, 28, 128), 1, 512, 1),
    ("s1b0_down", (56, 56, 256), 1, 512, 2),
    ("s1_c1", (28, 28, 512), 1, 128, 1),
    ("s1_c2", (28, 28, 128), 3, 128, 1),
    ("s2b0_c1", (28, 28, 512), 1, 256, 1),
    ("s2b0_c2", (28, 28, 256), 3, 256, 2),
    ("s2_c3", (14, 14, 256), 1, 1024, 1),
    ("s2b0_down", (28, 28, 512), 1, 1024, 2),
    ("s2_c1", (14, 14, 1024), 1, 256, 1),
    ("s2_c2", (14, 14, 256), 3, 256, 1),
    ("s3b0_c1", (14, 14, 1024), 1, 512, 1),
    ("s3b0_c2", (14, 14, 512), 3, 512, 2),
    ("s3_c3", (7, 7, 512), 1, 2048, 1),
    ("s3b0_down", (14, 14, 1024), 1, 2048, 2),
    ("s3_c1", (7, 7, 2048), 1, 512, 1),
    ("s3_c2", (7, 7, 512), 3, 512, 1),
]


def _has_relu(name):
    return not name.endswith(("_c3", "_down"))


def test_planes_are_the_builders():
    model = resnet.resnet50()
    built = {(v.parents[0].shape, v.layer.kernel_size[0],
              v.layer.nb_filter, v.layer.subsample[0])
             for v in model._order
             if isinstance(v.layer, Convolution2D)}
    assert built == {p[1:] for p in PLANES}
    assert len(PLANES) == len(built)


# ---------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------

def _same_pad(size, k, s):
    out = -(-size // s)
    total = max((out - 1) * s + k - size, 0)
    return out, total // 2, total - total // 2


def ref_conv(x, w, stride):
    """TensorFlow-'same' convolution, NHWC x HWIO, as a sum over
    kernel taps of (strided slice of the padded input) @ w[tap]."""
    k = w.shape[0]
    n, h, wd, _ = x.shape
    ho, lo_h, hi_h = _same_pad(h, k, stride)
    wo, lo_w, hi_w = _same_pad(wd, k, stride)
    x = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))
    y = 0.0
    for dh in range(k):
        for dw in range(k):
            patch = x[:, dh:dh + (ho - 1) * stride + 1:stride,
                      dw:dw + (wo - 1) * stride + 1:stride, :]
            y = y + jnp.einsum("nhwc,cf->nhwf", patch, w[dh, dw],
                               precision=HI)
    return y


def ref_bn(y, bn, training):
    """-> (normalised y, new moving mean, new moving variance)."""
    mm, mv = bn["_state"]["moving_mean"], bn["_state"]["moving_var"]
    if training:
        mean = jnp.mean(y, (0, 1, 2))
        var = jnp.mean(jnp.square(y - mean), (0, 1, 2))
        mm = MOMENTUM * mm + (1 - MOMENTUM) * mean
        mv = MOMENTUM * mv + (1 - MOMENTUM) * var
    else:
        mean, var = mm, mv
    out = (y - mean) / jnp.sqrt(var + EPS) * bn["gamma"] + bn["beta"]
    return out, mm, mv


def ref_plane(params, x, name, stride, relu, training=True):
    y = ref_conv(x, params[name]["kernel"], stride)
    out, mm, mv = ref_bn(y, params[name + "_bn"], training)
    return (jnp.maximum(out, 0) if relu else out), mm, mv


def _bn_params(rs, n):
    return {"gamma": jnp.asarray(1 + 0.2 * rs.randn(n), F32),
            "beta": jnp.asarray(0.2 * rs.randn(n), F32),
            "_state": {
                "moving_mean": jnp.asarray(0.1 * rs.randn(n), F32),
                "moving_var": jnp.asarray(
                    1 + 0.2 * rs.rand(n), F32)}}


def _kernel(rs, k, cin, cout):
    return jnp.asarray(
        rs.randn(k, k, cin, cout) * np.sqrt(2.0 / (k * k * cin)), F32)


def _fill(model, params):
    """``params`` in the model's own tree: the parameterless layers
    (activations, the add) have their empty entries there."""
    tree = model.init_params(jax.random.key(0))
    assert {k: jax.tree_util.tree_map(jnp.shape, v)
            for k, v in tree.items() if v} == \
        jax.tree_util.tree_map(jnp.shape, params)
    return {**tree, **params}


def _gap(got, want):
    """Norm of the difference over the norm of the reference."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all()
    return float(np.linalg.norm(got - want) /
                 max(np.linalg.norm(want), 1e-30))


# about four times the widest gap read here on the CPU. float32: the
# same sums in another order. mixed_bfloat16: 8 bits of mantissa on
# every activation (the input is rounded on both sides); a BatchNorm's
# input gradient is what is left once the mean and the projection on
# its output are taken out, so it keeps 4-6% of that rounding.
# "batch" is a batch statistic backed out of the moving update, which
# magnifies the update's own gap by 1 / (1 - momentum)
TOL = {
    "float32": dict(out=2e-6, moving=2e-6, batch=1e-4, grad=5e-6),
    "mixed_bfloat16": dict(out=2e-2, moving=1.5e-3, batch=1e-2,
                           grad=0.15),
}


def _plane(plane, policy):
    name, shape, k, filters, stride = plane
    rs = np.random.RandomState(len(name) + shape[0])
    inp = Input(shape, name="x")
    out = resnet.conv_bn(
        inp, filters, k, stride,
        activation="relu" if _has_relu(name) else None, name="p")
    model = Model(inp, out)
    params = _fill(model, {
        "p": {"kernel": _kernel(rs, k, shape[-1], filters)},
        "p_bn": _bn_params(rs, filters)})
    x = jnp.asarray(rs.randn(2, *shape), F32)
    if policy == "mixed_bfloat16":
        x = x.astype(BF16)
    g = jnp.asarray(rs.randn(2, -(-shape[0] // stride),
                             -(-shape[1] // stride), filters), F32)
    return model, params, x, g


@pytest.mark.parametrize("policy", ["float32", "mixed_bfloat16"])
@pytest.mark.parametrize("plane", PLANES, ids=[p[0] for p in PLANES])
def test_plane_forward_and_moving_statistics(plane, policy):
    name, _, _, _, stride = plane
    model, params, x, _ = _plane(plane, policy)
    out, upd = jax.jit(
        lambda p, a: model.apply(p, a, training=True))(params, x)
    want, mm, mv = jax.jit(
        lambda p, a: ref_plane(p, a.astype(F32), "p", stride,
                               _has_relu(name)))(params, x)
    assert out.dtype == x.dtype and out.shape == want.shape
    tol = TOL[policy]
    assert _gap(out, want) <= tol["out"]
    state = upd["p_bn"]["_state"]
    assert set(upd) == {"p_bn"} and set(state) == {
        "moving_mean", "moving_var"}
    assert _gap(state["moving_mean"], mm) <= tol["moving"]
    assert _gap(state["moving_var"], mv) <= tol["moving"]
    # the batch statistics themselves, back out of the update
    old = params["p_bn"]["_state"]
    for key, new_ref in (("moving_mean", mm), ("moving_var", mv)):
        batch = (state[key] - MOMENTUM * old[key]) / (1 - MOMENTUM)
        batch_ref = (new_ref - MOMENTUM * old[key]) / (1 - MOMENTUM)
        assert _gap(batch, batch_ref) <= tol["batch"]


@pytest.mark.parametrize("policy", ["float32", "mixed_bfloat16"])
@pytest.mark.parametrize("plane", PLANES, ids=[p[0] for p in PLANES])
def test_plane_gradients(plane, policy):
    name, _, _, _, stride = plane
    model, params, x, g = _plane(plane, policy)

    def loss(p, a):
        out, _ = model.apply(p, a, training=True)
        return jnp.sum(out.astype(F32) * g)

    def ref_loss(p, a):
        out, _, _ = ref_plane(p, a, "p", stride, _has_relu(name))
        return jnp.sum(out * g)

    gp, gx = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)
    rp, rx = jax.jit(jax.grad(ref_loss, argnums=(0, 1)))(
        params, x.astype(F32))
    assert gx.dtype == x.dtype
    tol = TOL[policy]["grad"]
    assert _gap(gx, rx) <= tol
    assert _gap(gp["p"]["kernel"], rp["p"]["kernel"]) <= tol
    assert _gap(gp["p_bn"]["gamma"], rp["p_bn"]["gamma"]) <= tol
    assert _gap(gp["p_bn"]["beta"], rp["p_bn"]["beta"]) <= tol
    # moving statistics are state, not parameters
    assert not np.asarray(gp["p_bn"]["_state"]["moving_mean"]).any()


# ---------------------------------------------------------------------
# strided convolution gradients against float64 numpy
# ---------------------------------------------------------------------

def _np_conv_vjp(x, w, g, stride, padding):
    """y, dx, dw of the NHWC x HWIO convolution in float64, the
    adjoints written out tap by tap."""
    x, w, g = (np.asarray(a, np.float64) for a in (x, w, g))
    k = w.shape[0]
    n, h, wd, _ = x.shape
    if padding == "SAME":
        ho, lo_h, hi_h = _same_pad(h, k, stride)
        wo, lo_w, hi_w = _same_pad(wd, k, stride)
    else:
        ho, wo = (h - k) // stride + 1, (wd - k) // stride + 1
        lo_h = hi_h = lo_w = hi_w = 0
    xp = np.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))
    y = np.zeros((n, ho, wo, w.shape[-1]))
    dxp, dw = np.zeros_like(xp), np.zeros_like(w)
    for dh in range(k):
        for dv in range(k):
            rows = slice(dh, dh + (ho - 1) * stride + 1, stride)
            cols = slice(dv, dv + (wo - 1) * stride + 1, stride)
            y += xp[:, rows, cols, :] @ w[dh, dv]
            dxp[:, rows, cols, :] += g @ w[dh, dv].T
            dw[dh, dv] = np.einsum("nhwc,nhwf->cf",
                                   xp[:, rows, cols, :], g)
    dx = dxp[:, lo_h:lo_h + h, lo_w:lo_w + wd, :]
    return y, dx, dw


def _layer_conv_vjp(x, w, g, stride, padding):
    layer = Convolution2D(w.shape[-1], w.shape[0], w.shape[1],
                          subsample=stride, bias=False,
                          border_mode=padding.lower())

    def f(x, w):
        return layer.call({"kernel": w}, x)
    y, vjp = jax.vjp(f, x, w)
    return (y,) + vjp(g.astype(y.dtype))


@pytest.mark.parametrize("stride", [1, 2, 3])
@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
@pytest.mark.parametrize("hw", [(8, 8), (9, 11)])
def test_convolution2d_grads_match_float64(stride, k, padding, hw,
                                           rng):
    x = jnp.asarray(rng.randn(2, *hw, 5), F32)
    w = jnp.asarray(rng.randn(k, k, 5, 7), F32)
    ho, wo = (-(-n // stride) if padding == "SAME"
              else (n - k) // stride + 1 for n in hw)
    g = jnp.asarray(rng.randn(2, ho, wo, 7), F32)
    want = _np_conv_vjp(x, w, g, stride, padding)
    got = _layer_conv_vjp(x, w, g, stride, padding)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-4,
                                   atol=1e-4)


def test_convolution2d_grads_bf16(rng):
    x = jnp.asarray(rng.randn(2, 12, 12, 8), BF16)
    w = jnp.asarray(rng.randn(3, 3, 8, 16), BF16)
    g = jnp.asarray(rng.randn(2, 6, 6, 16), BF16)
    y, dx, dw = _layer_conv_vjp(x, w, g, 2, "SAME")
    assert y.dtype == dx.dtype == dw.dtype == BF16
    want = _np_conv_vjp(x.astype(F32), w.astype(F32),
                          g.astype(F32), 2, "SAME")
    for a, b in zip((y, dx, dw), want):
        assert _gap(a, b) <= 1e-2


# ---------------------------------------------------------------------
# the bottleneck block
# ---------------------------------------------------------------------

def _ref_bottleneck(params, x, stride, downsample, training):
    upd = {}

    def plane(h, name, s, relu):
        out, mm, mv = ref_plane(params, h, name, s, relu, training)
        upd[name + "_bn"] = (mm, mv)
        return out
    y = plane(x, "b_c1", 1, True)
    y = plane(y, "b_c2", stride, True)
    y = plane(y, "b_c3", 1, False)
    sc = plane(x, "b_down", stride, False) if downsample else x
    return jnp.maximum(y + sc, 0), upd


@pytest.mark.parametrize("training", [True, False],
                         ids=["training", "inference"])
@pytest.mark.parametrize("policy", ["float32", "mixed_bfloat16"])
@pytest.mark.parametrize("stride,downsample", [
    (1, False), (1, True), (2, True)],
    ids=["identity", "shortcut_conv", "shortcut_conv_stride2"])
def test_bottleneck_against_reference(stride, downsample, policy,
                                      training):
    filters, hw = 16, 16
    cin = 4 * filters if not downsample else 32
    rs = np.random.RandomState(7 + stride + 2 * downsample)
    inp = Input((hw, hw, cin), name="x")
    model = Model(inp, resnet._bottleneck(
        inp, filters, stride=stride, downsample=downsample, name="b"))
    convs = [("b_c1", 1, cin, filters), ("b_c2", 3, filters, filters),
             ("b_c3", 1, filters, 4 * filters)]
    if downsample:
        convs.append(("b_down", 1, cin, 4 * filters))
    params = {}
    for name, k, ci, co in convs:
        params[name] = {"kernel": _kernel(rs, k, ci, co)}
        params[name + "_bn"] = _bn_params(rs, co)
    params = _fill(model, params)
    x = jnp.asarray(rs.randn(2, hw, hw, cin), F32)
    if policy == "mixed_bfloat16":
        x = x.astype(BF16)
    out, upd = jax.jit(lambda p, a: model.apply(
        p, a, training=training))(params, x)
    want, ref_upd = jax.jit(
        lambda p, a: _ref_bottleneck(p, a.astype(F32), stride,
                                     downsample, training))(params, x)
    hw_out = hw // stride
    assert out.shape == (2, hw_out, hw_out, 4 * filters)
    assert out.dtype == x.dtype
    tol = TOL[policy]
    assert _gap(out, want) <= tol["out"]
    if not training:
        assert not jax.tree_util.tree_leaves(upd)
        return
    assert set(upd) == set(ref_upd)
    for name, (mm, mv) in ref_upd.items():
        state = upd[name]["_state"]
        assert _gap(state["moving_mean"], mm) <= tol["moving"]
        assert _gap(state["moving_var"], mv) <= tol["moving"]


# ---------------------------------------------------------------------
# what is gone stays gone
# ---------------------------------------------------------------------

def test_resnet50_has_no_fused_argument():
    with pytest.raises(TypeError):
        resnet.resnet50(fused=True)
    with pytest.raises(TypeError):
        resnet.ResNet(50).build(fused=False)
