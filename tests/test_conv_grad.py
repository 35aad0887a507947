"""Phase-decomposed strided-conv backward (ops.conv_grad) and
maxpool mask backward (ops.pool_grad).

The conv tests pin the tentpole claim: the phase backward computes
the SAME sums as jax's transpose rule (strict f32 agreement at
strides 1 and 2, SAME/VALID, odd/even extents) while emitting only
stride-1 convs over undilated operands — no `lhs_dilation` (dx) or
`rhs_dilation` (dw) conv remains in the trained ResNet-50 step, and
the executed-FLOPs count (perf.flops — HloCostAnalysis discounts
dilation zeros and provably reports a 0% change) drops >=20%."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import conv_grad, pool_grad

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

_DN = ("NHWC", "HWIO", "NHWC")


def _vjp_pair(f, x, w, g):
    _, vjp = jax.vjp(f, x, w)
    return vjp(g)


def _lax_conv(stride, padding):
    def f(x, w):
        return jax.lax.conv_general_dilated(
            x, w, stride, padding, dimension_numbers=_DN)
    return f


@pytest.mark.parametrize("stride", [1, 2, 3])
@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
@pytest.mark.parametrize("hw", [(8, 8), (9, 11)])
def test_conv2d_grads_match_transpose_rule(stride, k, padding, hw,
                                           rng):
    if k == 1 and padding == "SAME" and hw == (9, 11):
        pass  # keep: odd extents with k=1 exercise M*s > H cropping
    h, w_ = hw
    x = jnp.asarray(rng.randn(2, h, w_, 5), jnp.float32)
    w = jnp.asarray(rng.randn(k, k, 5, 7), jnp.float32)
    s = (stride, stride)
    ref_f = _lax_conv(s, padding)
    y = ref_f(x, w)
    g = jnp.asarray(rng.randn(*y.shape), jnp.float32)

    dx_ref, dw_ref = _vjp_pair(ref_f, x, w, g)
    dx, dw = _vjp_pair(
        lambda x, w: conv_grad.conv2d(x, w, stride=s,
                                      padding=padding,
                                      phase_bwd=True), x, w, g)
    # strict f32: same sums, reassociated — tolerance is rounding
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=1e-5, atol=1e-5)


def test_conv2d_grads_bf16(rng):
    x = jnp.asarray(rng.randn(2, 12, 12, 8), jnp.bfloat16)
    w = jnp.asarray(rng.randn(3, 3, 8, 16) * 0.1, jnp.bfloat16)
    s = (2, 2)
    ref_f = _lax_conv(s, "SAME")
    g = jnp.asarray(rng.randn(2, 6, 6, 16), jnp.bfloat16)
    dx_ref, dw_ref = _vjp_pair(ref_f, x, w, g)
    dx, dw = _vjp_pair(
        lambda x, w: conv_grad.conv2d(x, w, stride=s,
                                      phase_bwd=True), x, w, g)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(dx, np.float32), np.asarray(dx_ref, np.float32),
        rtol=0.1, atol=0.1)
    np.testing.assert_allclose(
        np.asarray(dw, np.float32), np.asarray(dw_ref, np.float32),
        rtol=0.1, atol=0.2)


def test_phase_flag_gates_backward(rng, monkeypatch):
    x = jnp.asarray(rng.randn(1, 8, 8, 4), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 4, 4), jnp.float32)

    def loss(x, w):
        return jnp.sum(conv_grad.conv2d(x, w, stride=(2, 2)))

    def bumps():
        before = dict(conv_grad.invocations)
        jax.grad(loss, argnums=(0, 1))(x, w)
        return {k: conv_grad.invocations[k] - before[k]
                for k in before}

    # default on CPU: MEASURED_WIN gate is off -> transpose rule
    monkeypatch.delenv("ZOO_TPU_PHASE_BWD", raising=False)
    d = bumps()
    assert d["bwd_ref"] == 1 and d["bwd_phase"] == 0
    monkeypatch.setenv("ZOO_TPU_PHASE_BWD", "1")
    d = bumps()
    assert d["bwd_phase"] == 1 and d["bwd_ref"] == 0
    monkeypatch.setenv("ZOO_TPU_PHASE_BWD", "0")  # explicit revert
    d = bumps()
    assert d["bwd_ref"] == 1 and d["bwd_phase"] == 0


def test_conv_bn_stride2_phase_matches_dilated(rng, monkeypatch):
    # the bf16 custom-VJP in ops.conv_bn dispatches the same phase
    # helpers; on/off must agree (identical sums, reassociated)
    from analytics_zoo_tpu.ops.conv_bn import conv3x3_bn

    x = jnp.asarray(rng.randn(2, 8, 8, 64), jnp.bfloat16)
    w = jnp.asarray(rng.randn(3, 3, 64, 64) * 0.05, jnp.bfloat16)
    sh = jnp.zeros((1, 64), jnp.float32)

    def loss(x, w):
        y, sm, sq = conv3x3_bn(x, w, stat_shift=sh, stride=2,
                               interpret=True)
        return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(sm) +
                1e-3 * jnp.sum(sq))

    grads = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("ZOO_TPU_PHASE_BWD", flag)
        grads[flag] = jax.grad(loss, argnums=(0, 1))(x, w)
    for a, b in zip(grads["0"], grads["1"]):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- #
# trained-step structure + executed FLOPs (the acceptance check)    #
# ---------------------------------------------------------------- #

def _conv_params(jaxpr, out):
    """All conv_general_dilated eqn params, recursing into sub-
    jaxprs (scan/cond/custom_vjp bodies)."""
    from jax.extend import core
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            out.append(eqn.params)
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for sub in vs:
                if isinstance(sub, core.ClosedJaxpr):
                    _conv_params(sub.jaxpr, out)
                elif isinstance(sub, core.Jaxpr):
                    _conv_params(sub, out)
    return out


def _lowered_resnet_step(image, batch, phase, monkeypatch):
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.models.image.imageclassification import (
        resnet50)
    from analytics_zoo_tpu.ops import losses, optimizers
    from bench import _resnet_train_chain

    init_nncontext(tpu_mesh={"data": 1}, devices=jax.devices()[:1],
                   log_level="WARNING")
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, image, image, 3), jnp.bfloat16)
    y = jnp.asarray(rs.randint(0, 1000, size=(batch, 1)), jnp.int32)
    tx = optimizers.SGD(lr=0.1, momentum=0.9).to_optax()
    monkeypatch.setenv("ZOO_TPU_PHASE_BWD", phase)
    model = resnet50(input_shape=(image, image, 3), classes=1000,
                     space_to_depth=False, fused=False)
    params = model.init_params(jax.random.PRNGKey(0), device="host")
    step, _ = _resnet_train_chain(
        model, tx, losses.softmax_cross_entropy, 1)
    opt_state = tx.init(params)
    jaxpr = jax.make_jaxpr(step)(params, opt_state, x, y)
    lowered = jax.jit(step).lower(params, opt_state, x, y)
    return jaxpr, lowered


def test_resnet_step_phase_removes_dilated_convs_and_flops(
        monkeypatch):
    """ISSUE acceptance: with ZOO_TPU_PHASE_BWD=1 the ResNet-50 train
    step contains no dilated conv (jaxpr AND HLO) and its executed-
    semantics FLOPs drop >=20% vs the transpose-rule backward.

    NOTE raw `compiled.cost_analysis()` cannot verify this:
    HloCostAnalysis discounts window positions that read padding or
    dilation-inserted zeros, so it reports the SAME count for both
    backwards (measured: 0.0% change). perf.flops counts what a
    systolic conv unit executes — see PERF.md round 7."""
    from analytics_zoo_tpu.perf import flops as pf

    jaxpr_off, low_off = _lowered_resnet_step(96, 1, "0", monkeypatch)
    convs_off = _conv_params(jaxpr_off.jaxpr, [])
    assert any(p["lhs_dilation"] != (1, 1) for p in convs_off), \
        "transpose-rule backward should contain dilated dx convs"

    jaxpr_on, low_on = _lowered_resnet_step(96, 1, "1", monkeypatch)
    convs_on = _conv_params(jaxpr_on.jaxpr, [])
    assert convs_on, "no convs found — jaxpr walk is broken"
    bad = [p for p in convs_on
           if p["lhs_dilation"] != (1, 1)
           or p["rhs_dilation"] != (1, 1)]
    assert not bad, f"{len(bad)} dilated convs remain: {bad[:2]}"

    off = pf.executed_flops(pf.hlo_text(low_off))
    on = pf.executed_flops(pf.hlo_text(low_on))
    drop = (off - on) / off
    assert drop >= 0.20, \
        f"executed FLOPs {off:.3e} -> {on:.3e}: {drop:.1%} < 20%"
    # and the HLO-level view agrees with the jaxpr walk
    assert not any("dilate" in o.detail
                   for o in pf.parse_hlo_ops(pf.hlo_text(low_on)))
    # executed ~= model once the structural waste is gone (2x: the
    # 4.09e9 analytic constant counts MACs, executed counts 2/MAC)
    model_f = 2.0 * 3 * 4.09e9 * (96 / 224.0) ** 2
    assert 1.2 < off / model_f < 1.5
    assert 0.9 < on / model_f < 1.1


# ---------------------------------------------------------------- #
# maxpool mask backward                                            #
# ---------------------------------------------------------------- #

@pytest.mark.parametrize("pool,stride,padding", [
    ((2, 2), (2, 2), "VALID"), ((3, 3), (2, 2), "SAME"),
    ((3, 3), (1, 1), "SAME"), ((2, 3), (2, 1), "VALID")])
def test_maxpool_grads_match_select_and_scatter(pool, stride,
                                                padding, rng):
    # tie-free input: mask backward must equal jax's reduce_window
    # VJP (select_and_scatter) exactly
    x = jnp.asarray(np.argsort(rng.rand(2 * 9 * 11 * 3))
                    .reshape(2, 9, 11, 3), jnp.float32)

    def ref(x):
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1,) + pool + (1,),
            (1,) + stride + (1,), padding)

    def ours(x):
        return pool_grad.maxpool2d(x, pool, stride, padding)

    y_ref = ref(x)
    np.testing.assert_array_equal(np.asarray(ours(x)),
                                  np.asarray(y_ref))
    g = jnp.asarray(rng.randn(*y_ref.shape), jnp.float32)
    dx_ref = jax.vjp(ref, x)[1](g)[0]
    dx = jax.vjp(ours, x)[1](g)[0]
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-6, atol=1e-6)


def test_maxpool_tie_splits_equally():
    # equal maxima share the cotangent (select_and_scatter instead
    # routes everything to the first max — a subgradient choice that
    # starves tied activations; documented in ops.pool_grad)
    x = jnp.ones((1, 4, 4, 1), jnp.float32)
    dx = jax.grad(lambda x: jnp.sum(
        pool_grad.maxpool2d(x, (2, 2), (2, 2), "VALID")))(x)
    np.testing.assert_allclose(np.asarray(dx),
                               np.full((1, 4, 4, 1), 0.25))
    # two-way tie inside one window
    x2 = jnp.asarray(
        np.array([[3.0, 3.0], [1.0, 0.0]]).reshape(1, 2, 2, 1),
        jnp.float32)
    dx2 = jax.grad(lambda x: jnp.sum(
        pool_grad.maxpool2d(x, (2, 2), (2, 2), "VALID")))(x2)
    np.testing.assert_allclose(
        np.asarray(dx2).reshape(2, 2),
        np.array([[0.5, 0.5], [0.0, 0.0]]))


def test_maxpool_mass_conservation(rng):
    # non-overlapping windows: the routed cotangent mass is exactly
    # the incoming mass, ties or not
    x = jnp.asarray(rng.randint(0, 3, size=(2, 8, 8, 4)),
                    jnp.float32)

    def loss(x):
        y = pool_grad.maxpool2d(x, (2, 2), (2, 2), "VALID")
        return jnp.sum(y * 2.0)

    dx = jax.grad(loss)(x)
    np.testing.assert_allclose(float(jnp.sum(dx)),
                               2.0 * 4 * 4 * 2 * 4, rtol=1e-6)


def test_maxpool_layer_flag_revert(rng, monkeypatch):
    from analytics_zoo_tpu.pipeline.api.keras import layers as L

    x = jnp.asarray(np.argsort(rng.rand(2 * 8 * 8 * 3))
                    .reshape(2, 8, 8, 3), jnp.float32)
    lyr = L.MaxPooling2D(pool_size=2)
    params = lyr.init(jax.random.key(0), (8, 8, 3))

    def grad_with(flag):
        if flag is None:
            monkeypatch.delenv("ZOO_TPU_MAXPOOL_MASK_BWD",
                               raising=False)
        else:
            monkeypatch.setenv("ZOO_TPU_MAXPOOL_MASK_BWD", flag)
        before = pool_grad.invocations["fwd"]
        dx = jax.grad(lambda x: jnp.sum(lyr.call(params, x)))(x)
        return dx, pool_grad.invocations["fwd"] - before

    dx_on, used_on = grad_with(None)     # default: mask backward ON
    dx_off, used_off = grad_with("0")    # revert: reduce_window path
    assert used_on == 1 and used_off == 0
    np.testing.assert_allclose(np.asarray(dx_on),
                               np.asarray(dx_off),
                               rtol=1e-6, atol=1e-6)


def test_maxpool_dtype_preserved(rng):
    x = jnp.asarray(rng.randn(1, 6, 6, 2), jnp.bfloat16)
    y = pool_grad.maxpool2d(x, (2, 2), (2, 2), "SAME")
    assert y.dtype == jnp.bfloat16
    dx = jax.grad(lambda x: jnp.sum(pool_grad.maxpool2d(
        x, (2, 2), (2, 2), "SAME").astype(jnp.float32)))(x)
    assert dx.dtype == jnp.bfloat16
