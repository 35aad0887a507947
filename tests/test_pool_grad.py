"""Max-pool mask backward (ops.pool_grad): equal to jax's
`select_and_scatter` rule on tie-free input, ties split equally,
cotangent mass conserved, dtype kept, and the layer's flag reverts
to the transpose rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import pool_grad


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
