"""Prefill computes the prompts it is given and nothing else: the rows
of `prefill` are the requests being admitted, addressed to their
cache slots by ``slots``, and `GenerationEngine.admit` runs one
one-row program a request at that request's own bucket. Both
decoders (`TransformerLayer` over K/V pools, float and int8;
`PatternDecoder` over its latent pool) at toy sizes on the CPU: a row
prefilled into a LIVE batch leaves every other slot bit for bit what
it was, writes the pages of its own slot only, and samples the token
the whole-batch call (``slots=None``, `generate`'s) samples for it.
"""

import numpy as np
import pytest

from analytics_zoo_tpu import init_nncontext
from analytics_zoo_tpu.common import tracing
from analytics_zoo_tpu.pipeline.inference import (ContinuousBatcher,
                                                  GenerationEngine)
from analytics_zoo_tpu.pipeline.inference.batching import bucket_ladder
from analytics_zoo_tpu.pipeline.inference.generation import (
    PROMPT_BUCKET_FLOOR, prompt_ladder)

CTX, PAGE, SLOTS = 128, 8, 4
# (decoder, cache dtype): a latent row has no heads, so no int8
CASES = [("transformer", "f32"), ("transformer", "int8"),
         ("latent", "f32")]
KINDS = ["transformer", "latent"]


def _net(kind):
    """(net, params, vocab) at toy widths, `CTX` positions."""
    import jax
    init_nncontext(seed=0, log_level="WARNING")
    if kind == "transformer":
        from analytics_zoo_tpu.pipeline.api.keras.layers.transformer \
            import TransformerLayer
        net = TransformerLayer(n_block=2, hidden_size=32, n_head=2,
                               seq_len=CTX, vocab=61,
                               hidden_p_drop=0.0, attn_p_drop=0.0,
                               embed_p_drop=0.0)
        return net, net.build(jax.random.key(0), (CTX,)), 61
    from analytics_zoo_tpu.pipeline.api.keras.layers import \
        deepseek_v2_decoder
    cfg = dict(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, first_k_dense_replace=1, moe_layer_freq=1,
        n_routed_experts=16, n_shared_experts=2, num_experts_per_tok=3,
        n_group=4, topk_group=2, routed_scaling_factor=2.0,
        num_attention_heads=8, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rms_norm_eps=1e-6, rope_theta=10000, vocab_size=100,
        max_position_embeddings=CTX, initializer_range=0.2)
    net = deepseek_v2_decoder(cfg)
    return net, net.build(jax.random.key(0), (CTX,)), 100


def _pools(cache):
    """{name: array} of every page pool of either cache."""
    names = ("pages",) if hasattr(cache, "pages") else \
        ("k_pages", "v_pages", "k_scales", "v_scales")
    return {n: np.asarray(getattr(cache, n)) for n in names
            if getattr(cache, n) is not None}


def _shuffled_cache(net, kv):
    """A fresh cache whose slots hold scattered physical pages, so
    that an address that skipped the table would land elsewhere."""
    import jax.numpy as jnp
    cache = net.init_kv_cache(SLOTS, CTX, page_size=PAGE,
                              dtype={"f32": jnp.float32,
                                     "int8": jnp.int8}[kv])
    table = np.random.RandomState(7).permutation(
        cache.num_pages).astype(np.int32).reshape(
            cache.page_table.shape)
    return cache._replace(page_table=jnp.asarray(table)), table


def _prompts(vocab, lens):
    rs = np.random.RandomState(11)
    ids = np.zeros((len(lens), CTX), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rs.randint(1, vocab, size=n)
    return ids


@pytest.mark.parametrize("kind,kv", CASES)
def test_one_row_prefill_into_a_live_batch(kind, kv):
    import jax
    import jax.numpy as jnp
    net, params, vocab = _net(kind)
    cache0, table = _shuffled_cache(net, kv)
    lens = np.array([9, 40, 23, 70], np.int32)
    ids = _prompts(vocab, lens)
    prefill = jax.jit(net.prefill)
    step = jax.jit(net.decode_step)

    # what the whole-batch call gives for every row
    whole, logits_whole = prefill(params, cache0, ids, lens)

    # a live batch: slots 0, 1, 3 hold prompts and are mid-decode
    others = np.array([0, 1, 3])
    active = jnp.asarray([True, True, False, True])
    live, lg = prefill(params, cache0, ids,
                       np.where(np.arange(SLOTS) == 2, 0, lens))
    for _ in range(2):
        live, lg = step(params, live, jnp.argmax(lg, -1).astype(
            jnp.int32), active=active)
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    assert int(live.seq_lens[2]) == 0

    # admit slot 2: one row, at its own bucket, addressed by slot
    after, logits = prefill(params, live, ids[2:3, :32], lens[2:3],
                            np.array([2], np.int32))
    assert logits.shape == (1, vocab)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(logits_whole[2]),
                               rtol=2e-5, atol=2e-5)
    assert int(jnp.argmax(logits[0])) == int(jnp.argmax(
        logits_whole[2]))

    # lengths: the admitted slot's alone
    np.testing.assert_array_equal(np.asarray(after.seq_lens)[others],
                                  np.asarray(live.seq_lens)[others])
    assert int(after.seq_lens[2]) == 23
    np.testing.assert_array_equal(np.asarray(after.page_table), table)

    # pages: only the admitted slot's first ceil(23 / 8) pages are
    # written, with the rows the whole-batch call writes there
    own = set(table[2, :3].tolist())
    for name, before in _pools(live).items():
        now = _pools(after)[name]
        changed = np.flatnonzero(
            (before != now).any(axis=(0, 2, 3)))
        assert set(changed.tolist()) == own, (name, changed, own)
        want = _pools(whole)[name][:, table[2, :3]]
        got = now[:, table[2, :3]]
        if now.dtype == np.int8:
            assert np.abs(got.astype(np.int32) - want).max() <= 1
        else:
            np.testing.assert_allclose(got, want, rtol=2e-5,
                                       atol=2e-5)
        # rows of the last page past the prompt keep what they held
        np.testing.assert_array_equal(now[:, table[2, 2], 23 % PAGE:],
                                      before[:, table[2, 2],
                                             23 % PAGE:])

    # the residents' next step: bit for bit what it would have been
    _, lg_before = step(params, live, tok, active=active)
    _, lg_after = step(params, after, tok, active=active)
    assert np.asarray(lg_before)[others].tobytes() == \
        np.asarray(lg_after)[others].tobytes()


@pytest.mark.parametrize("kind", KINDS)
def test_row_with_no_prompt_leaves_its_slot(kind):
    """A padding row (``prompt_lens == 0``) of a program with more
    rows than prompts: its slot is not touched either."""
    import jax
    net, params, vocab = _net(kind)
    cache0, _ = _shuffled_cache(net, "f32")
    ids = _prompts(vocab, [12, 30])
    prefill = jax.jit(net.prefill)
    live, _ = prefill(params, cache0, ids[:1, :32],
                      np.array([12], np.int32), np.array([3], np.int32))
    after, _ = prefill(params, live, ids[:, :32],
                       np.array([0, 30], np.int32),
                       np.array([3, 1], np.int32))
    assert np.asarray(after.seq_lens).tolist() == [0, 30, 0, 12]
    for name, before in _pools(live).items():
        now = _pools(after)[name]
        page3 = np.asarray(live.page_table)[3]
        np.testing.assert_array_equal(now[:, page3], before[:, page3])


def _engine(kind, kv="f32", **kw):
    net, params, vocab = _net(kind)
    kw.setdefault("max_slots", SLOTS)
    return GenerationEngine(net, params, max_context=CTX,
                            page_size=PAGE, cache_dtype=kv, **kw), vocab


def _decode(eng, slots, n):
    """``n`` more greedy tokens of every slot in ``slots``."""
    active = np.zeros((eng.max_slots,), np.bool_)
    active[list(slots)] = True
    out = {s: [] for s in slots}
    for _ in range(n):
        toks = eng.step(active)
        for s in slots:
            out[s].append(int(toks[s]))
    return out


@pytest.mark.parametrize("kind,kv", CASES)
def test_admit_runs_each_request_at_its_own_bucket(kind, kv):
    import jax
    eng, vocab = _engine(kind, kv)
    rs = np.random.RandomState(5)
    short = rs.randint(1, vocab, size=5).tolist()
    long = rs.randint(1, vocab, size=70).tolist()
    want = [[int(t) for t in eng.generate(p, max_new_tokens=4)[0]]
            for p in (short, long)]
    (s0, t0), (s1, t1) = eng.admit([(short, 4, 0.0), (long, 4, 0.0)])
    # two programs, one prompt row each, each at its own bucket
    assert sorted(eng._compiled_prefill) == [32, 128]
    assert eng.prefill_counts == (2, 2)
    for tp, fn in eng._compiled_prefill.items():
        shapes = [a.shape for a in jax.tree_util.tree_leaves(
            fn.args_info[0][2:7])]
        # the slots' last tokens, then the one prompt row
        assert shapes == [(eng.max_slots,), (1, tp), (1,), (1,),
                          (1,)], shapes
    more = _decode(eng, (s0, s1), 3)
    assert [t0] + more[s0] == want[0]
    assert [t1] + more[s1] == want[1]


@pytest.mark.parametrize("kind", KINDS)
def test_admission_between_steps_never_perturbs_residents(kind):
    eng, vocab = _engine(kind)
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, vocab, size=n).tolist()
               for n in (40, 7, 100)]
    want = [[int(t) for t in eng.generate(p, max_new_tokens=8)[0]]
            for p in prompts]
    got = {}
    (a, tok), = eng.admit([(prompts[0], 8, 0.0)])
    got[a] = [tok] + _decode(eng, (a,), 2)[a]
    (b, tok), = eng.admit([(prompts[1], 8, 0.0)])
    got[b] = [tok]
    for s, toks in _decode(eng, (a, b), 2).items():
        got[s] += toks
    (c, tok), = eng.admit([(prompts[2], 8, 0.0)])
    got[c] = [tok]
    for s, toks in _decode(eng, (a, b, c), 3).items():
        got[s] += toks
    assert got[a] == want[0]
    assert got[b] == want[1][:6]
    assert got[c] == want[2][:4]


def _naive_greedy(kind, net, params, prompt, max_new):
    """Uncached reference: the whole prefix forward for every token."""
    import jax.numpy as jnp
    ids, out = list(prompt), []
    for _ in range(max_new):
        h = net.call(params, jnp.asarray([ids], jnp.int32),
                     training=False)[0, len(ids) - 1]
        if kind == "transformer":
            h = h @ params["tok_embed"].T
        out.append(int(jnp.argmax(h)))
        ids.append(out[-1])
    return out


@pytest.mark.parametrize("kind", KINDS)
def test_generate_is_what_it_was(kind):
    """`generate` (every slot a prompt, ``slots=None``) against the
    uncached forward, prompts of two lengths in one batch."""
    import jax
    net, params, vocab = _net(kind)
    rs = np.random.RandomState(13)
    prompts = [rs.randint(1, vocab, size=n).tolist() for n in (6, 19)]
    ids = np.zeros((2, 32), np.int32)
    for row, p in enumerate(prompts):
        ids[row, :len(p)] = p
    plens = np.array([6, 19], np.int32)
    buf, lens = jax.jit(lambda p, i, n: net.generate(
        p, i, prompt_lens=n, max_new_tokens=5, page_size=PAGE))(
            params, ids, plens)
    buf, lens = np.asarray(buf), np.asarray(lens)
    for row, p in enumerate(prompts):
        assert buf[row, len(p):lens[row]].tolist() == \
            _naive_greedy(kind, net, params, p, 5)


@pytest.mark.parametrize("longest,want", [
    (16, (16,)), (32, (32,)), (100, (32, 64, 100)),
    (1024, (32, 64, 128, 256, 512, 1024)),
    (2048, (32, 64, 128, 256, 512, 1024, 2048))])
def test_prompt_ladder_starts_at_its_floor(longest, want):
    """Powers of two from the floor (a benchmark reader tells a
    16-slot decode step's grouped products from a prefill's by their
    96 rows: no bucket may be as short as 16), never more programs
    than the ladder from 1 had."""
    assert PROMPT_BUCKET_FLOOR >= 32
    assert prompt_ladder(longest) == want
    assert set(want) <= set(bucket_ladder(longest))
    assert len(want) <= len(bucket_ladder(longest))


@pytest.mark.parametrize("kind", KINDS)
def test_warm_compiles_the_ladder_and_traffic_compiles_nothing(kind):
    from jax import monitoring
    eng, vocab = _engine(kind)
    compiles, armed = [], [False]

    def listener(name, dur, **kw):
        if armed[0] and name.endswith("backend_compile_duration"):
            compiles.append(name)

    monitoring.register_event_duration_secs_listener(listener)
    cb = ContinuousBatcher(eng, queue_depth=32)
    rs = np.random.RandomState(3)
    try:
        cb.start()          # warm-up: the step and every bucket, AOT
        assert eng.prompt_buckets == (32, 64, 128)
        assert eng.stats()["warmed_programs"] == 1 + 3
        armed[0] = True
        sizes = [(1, 3), (31, 2), (33, 4), (64, 2), (65, 3), (120, 2),
                 (17, 5)]
        futs = [cb.submit(rs.randint(1, vocab, size=n).tolist(),
                          max_new_tokens=m) for n, m in sizes]
        for f, (_, m) in zip(futs, sizes):
            assert len(f.result(timeout=120)) == m
    finally:
        armed[0] = False
        cb.stop()
    assert not compiles
    assert eng.stats()["warmed_programs"] == 1 + 3


@pytest.mark.parametrize("kind", KINDS)
def test_prefill_span_counts_rows_and_calls(kind):
    """Three requests waiting when the loop starts are one admission:
    ``n`` 3, and a program a request (``calls`` 3) of one row each
    (``rows`` 3), ``bucket`` the longest of them."""
    eng, vocab = _engine(kind)
    cb = ContinuousBatcher(eng, queue_depth=8)
    rs = np.random.RandomState(2)
    tracing.reset_tracing()
    futs = [cb.submit(rs.randint(1, vocab, size=n).tolist(),
                      max_new_tokens=2) for n in (4, 50, 90)]
    cb.start()
    try:
        for f in futs:
            assert len(f.result(timeout=120)) == 2
    finally:
        cb.stop()
    spans = [r["fields"] for r in (
        rec.to_dict() for rec in tracing.get_store().records())
        if r["name"] == "decode/prefill"]
    assert sum(f["n"] for f in spans) == 3
    assert sum(f["prompt_tokens"] for f in spans) == 4 + 50 + 90
    for f in spans:
        assert f["rows"] == f["calls"] == f["n"]
    assert max(f["bucket"] for f in spans) == 128
