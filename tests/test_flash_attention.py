"""Pallas flash-attention kernel vs the dense XLA reference.

Runs the REAL kernel under the Pallas interpreter on the CPU test
mesh (ops/flash_attention.py auto-selects interpret off-TPU), so the
exact kernel code path is what's verified.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common.device import on_tpu
from analytics_zoo_tpu.ops.attention import dot_product_attention
from analytics_zoo_tpu.ops.flash_attention import (flash_attention,
                                                   supports)


def _qkv(b=2, t=256, h=4, d=64, dtype=jnp.float32, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, t, h, d) * 0.5, dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal, impl='xla')
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_matches_dense_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = dot_product_attention(q, k, v, causal=True, impl='xla')
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)


def test_cross_attention_lengths():
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(1, 128, 2, 32), jnp.float32)
    k = jnp.asarray(rs.randn(1, 384, 2, 32), jnp.float32)
    v = jnp.asarray(rs.randn(1, 384, 2, 32), jnp.float32)
    ref = dot_product_attention(q, k, v, impl='xla')
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_causal_cross_attention_end_aligned():
    # causal with Tq != Tk must follow the dense reference's
    # end-aligned convention (tril k=Tk-Tq: query i sees keys
    # <= i + Tk - Tq), not start-aligned — regression test for the
    # review-confirmed mismatch (max diff 2.3 before the fix)
    rs = np.random.RandomState(2)
    q = jnp.asarray(rs.randn(1, 128, 2, 32), jnp.float32)
    k = jnp.asarray(rs.randn(1, 384, 2, 32), jnp.float32)
    v = jnp.asarray(rs.randn(1, 384, 2, 32), jnp.float32)
    ref = dot_product_attention(q, k, v, causal=True, impl='xla')
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    out_auto = dot_product_attention(q, k, v, causal=True, impl='auto')
    np.testing.assert_allclose(np.asarray(out_auto), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_grad_matches_dense():
    q, k, v = _qkv(t=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            dot_product_attention(q, k, v, causal=True, impl='xla') ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_impl_selection():
    q, k, v = _qkv(t=128)
    out = dot_product_attention(q, k, v, impl="flash")
    ref = dot_product_attention(q, k, v, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # unsupported shape: 'flash' raises, 'auto' falls back
    qq = q[:, :100]
    with pytest.raises(ValueError):
        dot_product_attention(qq, k[:, :100], v[:, :100], impl="flash")
    out2 = dot_product_attention(qq, k[:, :100], v[:, :100],
                                 impl="auto")
    ref2 = dot_product_attention(qq, k[:, :100], v[:, :100], impl='xla')
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               atol=2e-5, rtol=2e-5)
    assert not supports(100, 100, 64, None)
    assert supports(256, 256, 64, None)
    assert not supports(256, 256, 64, jnp.ones((1, 1, 256, 256)))


def test_auto_is_default_and_backend_gated(monkeypatch):
    # flash is the DEFAULT path (VERDICT r2 #2): no env, no impl arg
    # → "auto", which routes to the kernel on TPU for Tk past the
    # measured crossover, and to dense on CPU (no interpret surprise)
    from analytics_zoo_tpu.ops import flash_attention as fa
    from analytics_zoo_tpu.ops.attention import (
        flash_backend_ok, flash_profitable, resolve_attention_impl)
    monkeypatch.delenv("ZOO_TPU_ATTENTION", raising=False)
    assert resolve_attention_impl(None) == "auto"
    # crossover policy (measured on v5e, PERF.md)
    monkeypatch.delenv("ZOO_TPU_FLASH_MIN_T", raising=False)
    assert not flash_profitable(512)
    assert flash_profitable(1024)
    monkeypatch.setenv("ZOO_TPU_FLASH_MIN_T", "256")
    assert flash_profitable(256)
    # off-TPU, auto stays dense even for qualifying shapes...
    monkeypatch.delenv("ZOO_TPU_FLASH_FORCE_INTERPRET", raising=False)
    q, k, v = _qkv(t=256, h=2, d=32)
    if not on_tpu():  # CPU test mesh
        assert not flash_backend_ok()
        before = fa.invocations
        dot_product_attention(q, k, v)       # default everything
        assert fa.invocations == before
    # ...and routes to the kernel when the backend gate is forced open
    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    assert flash_backend_ok()
    out = dot_product_attention(q, k, v)
    assert fa.invocations == before + 1
    ref = dot_product_attention(q, k, v, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_under_jit_and_vmapless_batch():
    q, k, v = _qkv(b=3, t=128, h=2, d=32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    out = f(q, k, v)
    ref = dot_product_attention(q, k, v, causal=True, impl='xla')
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_grad_causal_cross_attention():
    # Pallas backward must respect the end-aligned causal offset too
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(1, 128, 2, 32) * 0.5, jnp.float32)
    k = jnp.asarray(rs.randn(1, 384, 2, 32) * 0.5, jnp.float32)
    v = jnp.asarray(rs.randn(1, 384, 2, 32) * 0.5, jnp.float32)

    def loss(att):
        return lambda q, k, v: jnp.sum(att(q, k, v) ** 2)

    gf = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True, impl='xla')), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_grad_bf16():
    q, k, v = _qkv(t=128, dtype=jnp.bfloat16)
    g = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        dot_product_attention(q, k, v, causal=True,
                              impl='xla').astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=0.15, rtol=0.15)


def test_grad_causal_tq_gt_tk_masked_rows():
    # Tq > Tk causal: queries 0..Tq-Tk-1 are fully masked. When dead
    # and live rows SHARE a q-block (bf16 → 1024-blocks here), the
    # recomputed p must be the forward's uniform 1/l, not 1 — the
    # fused lse = m + log(l) absorbed log(l) at m=-1e30 and overscaled
    # dv by Tk (review-confirmed, dv err up to 56 before the fix)
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(1, 1024, 2, 32) * 0.5, jnp.bfloat16)
    k = jnp.asarray(rs.randn(1, 512, 2, 32) * 0.5, jnp.bfloat16)
    v = jnp.asarray(rs.randn(1, 512, 2, 32) * 0.5, jnp.bfloat16)

    gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
        q, k, v, causal=True, impl='xla').astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=0.2, rtol=0.2)


def test_causal_tq_gt_tk_dead_block_isolated():
    # f32 caps blocks at 512 (VMEM), so the Tq-Tk=512 dead rows form a
    # fully-masked q-block that the kernel SKIPS: those outputs are 0
    # and contribute nothing to any gradient (the dense reference
    # instead emits uniform-garbage attention for dead rows — its
    # values/grads there are meaningless, so isolation is the better
    # semantics). Live rows must still match dense exactly.
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(1, 1024, 2, 32) * 0.5, jnp.float32)
    k = jnp.asarray(rs.randn(1, 512, 2, 32) * 0.5, jnp.float32)
    v = jnp.asarray(rs.randn(1, 512, 2, 32) * 0.5, jnp.float32)
    dead = 512  # rows 0..511 see no keys (end-aligned causal)

    out = flash_attention(q, k, v, causal=True)
    ref = dot_product_attention(q, k, v, causal=True, impl='xla')
    assert float(jnp.max(jnp.abs(out[:, :dead]))) == 0.0
    np.testing.assert_allclose(np.asarray(out[:, dead:]),
                               np.asarray(ref[:, dead:]),
                               atol=2e-5, rtol=2e-5)

    gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
        q, k, v, causal=True, impl='xla') ** 2),
        argnums=(0, 1, 2))(q, k, v)
    # dq: dead rows get zero grad; live rows match dense
    assert float(jnp.max(jnp.abs(gf[0][:, :dead]))) == 0.0
    np.testing.assert_allclose(np.asarray(gf[0][:, dead:]),
                               np.asarray(gr[0][:, dead:]),
                               atol=5e-4, rtol=5e-4)
    # dk matches dense (dense passes no ds gradient at masked
    # positions either); dv differs only by dense's dead-row garbage
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gr[1]),
                               atol=5e-4, rtol=5e-4)
    assert np.isfinite(np.asarray(gf[2])).all()


def _padding_mask(b, tk, lengths):
    m = np.zeros((b, tk), np.float32)
    for i, ln in enumerate(lengths):
        m[i, :ln] = 1.0
    return m


def test_key_mask_matches_dense():
    q, k, v = _qkv(b=2, t=256)
    km = _padding_mask(2, 256, [256, 100])
    mask4 = km[:, None, None, :]              # BERT (B, 1, 1, Tk)
    ref = dot_product_attention(q, k, v, mask=mask4, impl='xla')
    out = flash_attention(q, k, v, key_mask=jnp.asarray(km))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # auto-routing: the (B,1,1,Tk) mask is detected as key-padding
    out_auto = dot_product_attention(q, k, v, mask=jnp.asarray(mask4),
                                     impl='auto')
    np.testing.assert_allclose(np.asarray(out_auto), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert supports(256, 256, 64, jnp.asarray(mask4), b=2)
    # per-query masks still fall back
    assert not supports(256, 256, 64, jnp.ones((2, 1, 256, 256)), b=2)
    # 2-D masks mean (Tq, Tk) in the dense path — never kernel-routed
    from analytics_zoo_tpu.ops.flash_attention import as_key_mask
    assert as_key_mask(jnp.ones((2, 256)), 2, 256) is None
    mask2d = jnp.asarray(np.tril(np.ones((256, 256), np.float32)))
    out2d = dot_product_attention(q, k, v, mask=mask2d, impl='auto')
    ref2d = dot_product_attention(q, k, v, mask=mask2d, impl='xla')
    np.testing.assert_allclose(np.asarray(out2d), np.asarray(ref2d),
                               atol=2e-5, rtol=2e-5)


def test_key_mask_with_causal_and_grad():
    q, k, v = _qkv(b=2, t=128, h=2, d=32, seed=9)
    km = jnp.asarray(_padding_mask(2, 128, [128, 77]))
    mask4 = km[:, None, None, :]

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       key_mask=km) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, mask=mask4, causal=True, impl='xla') ** 2)

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True, key_mask=km)),
        np.asarray(dot_product_attention(q, k, v, mask=mask4,
                                         causal=True, impl='xla')),
        atol=2e-5, rtol=2e-5)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_bert_padding_mask_flash_path():
    # BERT's (B, 1, 1, T) padding mask routes to the Pallas kernel
    # under attention_impl='auto' and matches the XLA path
    from analytics_zoo_tpu.pipeline.api.keras.layers.transformer import \
        BERT
    t, vocab = 128, 64
    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (2, t)).astype(np.int32)
    types = np.zeros((2, t), np.int32)
    pos = np.tile(np.arange(t), (2, 1)).astype(np.int32)
    mask = np.ones((2, t), np.float32)
    mask[1, 90:] = 0.0
    inputs = [ids, types, pos, mask]

    def run(impl):
        lay = BERT(vocab=vocab, hidden_size=32, n_block=1, n_head=2,
                   seq_len=t, intermediate_size=64,
                   output_all_block=False, attention_impl=impl)
        params = lay.init(jax.random.PRNGKey(0), None)
        outs = lay.call(params, [jnp.asarray(a) for a in inputs])
        return [np.asarray(o) for o in outs]

    ref = run("xla")
    out = run("auto")
    for a, b in zip(out, ref):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("tq,tk", [(128, 128), (256, 128), (128, 384)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_conformance_sweep(tq, tk, causal, masked):
    # fwd+grad conformance vs dense across the shape/mask grid (live
    # rows only where end-aligned causal creates none here: tk >= tq
    # or equal, so every row attends to something)
    rs = np.random.RandomState(tq + tk + causal + masked)
    q = jnp.asarray(rs.randn(2, tq, 2, 32) * 0.5, jnp.float32)
    k = jnp.asarray(rs.randn(2, tk, 2, 32) * 0.5, jnp.float32)
    v = jnp.asarray(rs.randn(2, tk, 2, 32) * 0.5, jnp.float32)
    km = None
    mask4 = None
    if masked:
        m = np.ones((2, tk), np.float32)
        m[1, tk // 2:] = 0.0
        km = jnp.asarray(m)
        mask4 = km[:, None, None, :]

    out = flash_attention(q, k, v, causal=causal, key_mask=km)
    ref = dot_product_attention(q, k, v, mask=mask4, causal=causal,
                                impl='xla')
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)

    gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal, key_mask=km) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
        q, k, v, mask=mask4, causal=causal, impl='xla') ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)
