"""The paged single-query attention kernel (`zoo_paged_decode`) under
the Pallas interpreter, against `decode_attention` over
`decode_view`'s dense gathered view: the same algorithm with pages,
not a view, as operands. Every geometry, length pattern and rule case
is a case of its own. Tier-1 fast.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops import attention as att
from analytics_zoo_tpu.ops import flash_attention as fa
from analytics_zoo_tpu.ops import kv_cache as kvc

SLOTS, CONTEXT, LAYERS = 4, 384, 2      # three 128-token blocks a slot
STALE = 1e4                             # what a row nobody may read holds

GEOMETRIES = [(25, 64, 16), (4, 32, 16), (2, 64, 32)]


def _patterns(page):
    """name -> (seq_lens, active, shuffle the page table)."""
    on = [True] * SLOTS
    return {
        "all_zero": ([0, 0, 0, 0], on, False),
        "one": ([1, 1, 1, 1], on, False),
        "boundary_minus_1": ([page - 1, 2 * page - 1, 127, 255], on,
                             False),
        "boundary": ([page, 2 * page, 128, 256], on, False),
        "boundary_plus_1": ([page + 1, 2 * page + 1, 129, 257], on,
                            False),
        "max_context_minus_1": ([CONTEXT - 1, 5, CONTEXT - 1, 200], on,
                                False),
        # a slot that holds tokens and sits the step out, a free slot
        # that does, and a full slot whose row has nowhere to land
        "mixed_inactive": ([70, 0, CONTEXT, 131],
                           [False, False, True, True], False),
        "shuffled_table": ([3, 140, 0, 300], on, True),
    }


def _filled_cache(heads, head_dim, page, lens, shuffle, dtype, seed):
    """A cache whose live rows are noise and whose every other row —
    past ``seq_lens`` inside a live page, and whole dead pages — holds
    ``STALE``."""
    rs = np.random.RandomState(seed)
    cache = kvc.init_cache(LAYERS, SLOTS, CONTEXT, heads, head_dim,
                           page_size=page, dtype=dtype)
    n_pool, pps = cache.num_pages, cache.page_table.shape[1]
    table = np.arange(n_pool, dtype=np.int32)
    if shuffle:
        table = rs.permutation(n_pool).astype(np.int32)
    table = table.reshape(SLOTS, pps)
    pools = []
    for _ in range(2):
        pool = np.full(cache.k_pages.shape, STALE, np.float32)
        for s, n in enumerate(lens):
            rows = rs.randn(LAYERS, n, heads * head_dim)
            for t in range(n):
                pool[:, table[s, t // page], t % page,
                     :heads * head_dim] = rows[:, t]
                pool[:, table[s, t // page], t % page,
                     heads * head_dim:] = 0.0
        pools.append(jnp.asarray(pool, dtype))
    return cache._replace(
        k_pages=pools[0], v_pages=pools[1],
        page_table=jnp.asarray(table),
        seq_lens=jnp.asarray(lens, jnp.int32))


def _both_paths(cache, heads, head_dim, active, dtype, seed, layer=1):
    rs = np.random.RandomState(seed + 1)
    q, k_new, v_new = (jnp.asarray(rs.randn(SLOTS, heads, head_dim),
                                   dtype) for _ in range(3))
    active = jnp.asarray(active)
    (k_ctx, v_ctx, _, _), rows = kvc.decode_view(
        cache, layer, k_new, v_new, active=active)
    dense = att.decode_attention(
        q, k_ctx.astype(dtype), v_ctx.astype(dtype),
        cache.seq_lens + active.astype(jnp.int32), impl="xla")
    k_row, v_row, _, _ = kvc.decode_rows(cache, k_new, v_new)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(rows[:2], (k_row, v_row)))
    paged = att.paged_decode_attention(
        q, k_row, v_row, cache, layer,
        kvc._decode_writes(cache, active))
    return np.asarray(dense, np.float32), np.asarray(paged, np.float32)


@pytest.mark.parametrize("pattern", sorted(_patterns(16)))
@pytest.mark.parametrize("heads,head_dim,page", GEOMETRIES)
def test_paged_kernel_matches_dense_view(heads, head_dim, page,
                                         pattern):
    lens, active, shuffle = _patterns(page)[pattern]
    cache = _filled_cache(heads, head_dim, page, lens, shuffle,
                          jnp.float32, seed=heads + page)
    before = fa.invocations
    dense, paged = _both_paths(cache, heads, head_dim, active,
                               jnp.float32, seed=heads)
    assert fa.invocations == before + 1
    # a slot with nothing to attend to (free and sitting out) is the
    # caller's to drop: uniform over stale rows there, zeros here
    live = np.asarray(lens) + np.asarray(active) > 0
    assert np.isfinite(paged).all()
    assert np.abs(paged[live]).max() < 10.0     # no stale row leaked
    np.testing.assert_allclose(paged[live], dense[live], rtol=1e-5,
                               atol=1e-5)
    assert not paged[~live].any()


def test_paged_kernel_bfloat16_pools():
    """The cells' dtype: bfloat16 pools and queries. The dense path
    rounds its scores to bfloat16 and the kernel keeps them in f32, so
    the two agree to bfloat16's step, and the kernel to a float32
    reference over the same rounded operands more closely."""
    heads, head_dim, page = 4, 32, 16
    lens, active, _ = _patterns(page)["shuffled_table"]
    cache = _filled_cache(heads, head_dim, page, lens, True,
                          jnp.bfloat16, seed=7)
    dense, paged = _both_paths(cache, heads, head_dim, active,
                               jnp.bfloat16, seed=7)
    np.testing.assert_allclose(paged, dense, rtol=0.05, atol=0.05)
    wide = cache._replace(k_pages=cache.k_pages.astype(jnp.float32),
                          v_pages=cache.v_pages.astype(jnp.float32))
    exact, _ = _both_paths(wide, heads, head_dim, active, jnp.float32,
                           seed=7)
    # same draws, rounded to bfloat16 first in one and not the other
    np.testing.assert_allclose(paged, exact, rtol=0.03, atol=0.03)


def test_paged_partial_of_an_empty_slot_is_the_merge_identity():
    heads, head_dim, page = 4, 32, 16
    cache = _filled_cache(heads, head_dim, page, [0, 17, 0, 0], False,
                          jnp.float32, seed=3)
    q = jnp.ones((SLOTS, heads * head_dim), jnp.float32)
    o, m, l = fa.paged_decode_partial(
        q, cache.k_pages, cache.v_pages, cache.page_table,
        cache.seq_lens, 0, heads=heads, head_dim=head_dim, scale=0.1)
    assert o.shape == (SLOTS, heads, head_dim)
    assert m.shape == l.shape == (SLOTS, heads)
    for s in (0, 2, 3):
        assert not np.asarray(o[s]).any() and not np.asarray(l[s]).any()
        assert (np.asarray(m[s]) == np.float32(-1e30)).all()
    assert (np.asarray(l[1]) > 0).all()


@pytest.mark.parametrize("case,want", [
    ("float32_page16", True), ("bfloat16_page16", True),
    ("float32_page8", True), ("bfloat16_page8", False),
    ("float32_page12", False), ("int8_page32", False),
    ("bfloat16_pool_float32_step", False), ("impl_xla", False),
    ("no_kernel_backend", False),
])
def test_rule_that_chooses_the_paged_path(monkeypatch, case, want):
    """Only what the step can observe: the backend, the selector, the
    pools' dtype against the activations', the page's rows."""
    if case == "no_kernel_backend":
        monkeypatch.delenv("ZOO_TPU_FLASH_FORCE_INTERPRET",
                           raising=False)
    else:
        monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    pool, _, page = case.partition("_page")
    pool = {"bfloat16_pool_float32_step": "bfloat16",
            "impl_xla": "float32",
            "no_kernel_backend": "float32"}.get(case, pool)
    cache = jax.eval_shape(lambda: kvc.init_cache(
        1, 2, 96, 2, 64, page_size=int(page or 16),
        dtype=jnp.dtype(pool)))
    step = "float32" if case == "bfloat16_pool_float32_step" else \
        ("bfloat16" if pool == "int8" else pool)
    got = att.paged_decode_ok(cache, jnp.dtype(step),
                              "xla" if case == "impl_xla" else None)
    assert got is want


def _toy(n_head=4, hidden=128, seq=64):
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.api.keras.layers.transformer \
        import TransformerLayer
    init_nncontext(seed=0)
    net = TransformerLayer(n_block=2, hidden_size=hidden, n_head=n_head,
                           seq_len=seq, vocab=61, hidden_p_drop=0.0,
                           attn_p_drop=0.0, embed_p_drop=0.0)
    return net, net.build(jax.random.key(0), (seq,))


def _greedy(net, params, steps):
    """Prefill three prompts of mixed length into four slots (one stays
    free), then ``steps`` decode steps, the last slot sitting out the
    second half: tokens (steps, S) and the final lengths."""
    prompts = np.zeros((4, 9), np.int32)
    plens = np.asarray([9, 3, 0, 6], np.int32)
    rs = np.random.RandomState(5)
    for i, n in enumerate(plens):
        prompts[i, :n] = rs.randint(1, 61, size=n)
    cache = net.init_kv_cache(4, 64, page_size=16)
    cache, logits = net.prefill(params, cache, jnp.asarray(prompts),
                                jnp.asarray(plens))
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    step = jax.jit(net.decode_step)
    out = []
    for i in range(steps):
        active = jnp.asarray([True, True, False, i < steps // 2])
        cache, logits = step(params, cache, tok, active)
        tok = jnp.where(active, jnp.argmax(logits, axis=-1), tok
                        ).astype(jnp.int32)
        out.append(np.asarray(tok))
    return np.stack(out), np.asarray(cache.seq_lens)


def test_decode_step_with_the_kernel_yields_the_dense_tokens(
        monkeypatch):
    """`TransformerLayer.decode_step` over 20 steps, the kernel forced
    under the interpreter against the CPU's dense path: the same
    greedy tokens, lengths and frozen slots."""
    net, params = _toy()
    monkeypatch.delenv("ZOO_TPU_FLASH_FORCE_INTERPRET", raising=False)
    before = fa.invocations
    dense, dense_lens = _greedy(net, params, 20)
    assert fa.invocations == before          # the CPU's path: no kernel
    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    paged, paged_lens = _greedy(net, params, 20)
    assert fa.invocations > before           # one trace of the scan body
    assert paged.tolist() == dense.tolist()
    assert paged_lens.tolist() == dense_lens.tolist() == [29, 23, 0, 16]


def test_kernel_path_traces_no_gather(monkeypatch):
    """The step as lowered: with the kernel, no gather through the page
    table is traced at all (nothing left for a compiler to remove);
    the dense path and int8 pools keep theirs."""
    net, params = _toy()

    def lowered(dtype):
        cache = net.init_kv_cache(4, 64, page_size=32, dtype=dtype)
        return jax.jit(net.decode_step).lower(
            params, cache, jnp.zeros((4,), jnp.int32)).as_text(
                debug_info=True)

    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    text = lowered(jnp.float32)
    assert "zoo:kv_cache/gather" not in text
    assert "zoo:kv_cache/append" in text
    assert "zoo:kv_cache/gather" in lowered(jnp.int8)
    monkeypatch.delenv("ZOO_TPU_FLASH_FORCE_INTERPRET")
    assert "zoo:kv_cache/gather" in lowered(jnp.float32)
