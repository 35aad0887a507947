"""`dots3_note_decoder` at a toy size against the benchmark's plain
float32 reference (`benchmark/reference/dots3_note.py`, which imports
nothing of the program): 6 layers in the published pattern (full,
full, sliding x 3, full; layer 0 dense), hidden 64; full layers of 8
heads over a latent of 16 + 8 with an indexer of 4 heads x 16 that
keeps 16 keys; sliding layers of 4 heads over a latent of 32 + 8 and
a window of 9; head gates, rescaled latents; 16 sigmoid-routed
experts, top-3 with a selection bias, one shared expert.

Contexts run to 70 tokens: past ``index_topk`` (the indexer really
selects) and past the window and the window pool's ring (its pages
are written over). Weights are float32 and the CPU multiplies float32
exactly, so what separates the two sides is the order of float32
sums; logits of magnitude 2-6 agree to 2e-4 unless such a sum flips a
key at the edge of the chosen 16, which the seeds here do not.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from analytics_zoo_tpu.common import observability as obs      # noqa: E402
from analytics_zoo_tpu.ops import kv_cache as kvc              # noqa: E402
from analytics_zoo_tpu.ops.attention import topk_mask          # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras import layers as L   # noqa: E402
from analytics_zoo_tpu.pipeline.inference.batching import (    # noqa: E402
    ContinuousBatcher)
from analytics_zoo_tpu.pipeline.inference.generation import (  # noqa: E402
    GenerationEngine)
from benchmark import weights_dots3 as wd                      # noqa: E402
from benchmark.reference import dots3_note as ref              # noqa: E402

F32 = jnp.float32
LOGIT_TOL = 2e-4
PATTERN = ["full_attention", "full_attention", "sliding_attention",
           "sliding_attention", "sliding_attention", "full_attention"]
TOY = dict(
    name="toy", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=6, n_layer=6,
    layer_types=PATTERN, first_k_dense_replace=1, moe_layer_freq=1,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=3,
    routed_scaling_factor=1, scoring_func="sigmoid",
    norm_topk_prob=True, topk_method="noaux_tc",
    num_attention_heads=8, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=80000000, rope_scaling=None,
    index_n_heads=4, index_head_dim=16, index_topk=16,
    swa_num_attention_heads=4, swa_q_lora_rank=24,
    swa_kv_lora_rank=32, swa_qk_nope_head_dim=24,
    swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=50000,
    sliding_window_size=9, attention_gate_type="headwise",
    swa_attention_gate_type="headwise",
    apply_mla_qkv_lora_rescale=True, rms_norm_eps=1e-5,
    vocab_size=100, max_position_embeddings=256,
    initializer_range=0.2, init={"router_bias_std": 0.05})
SEED = 2 ** 31 + 7
CHUNK = 8


def _share(first, count):
    return dict(TOY, n_routed_experts=count,
                published={"n_routed_experts": 16},
                held={"experts": [first, first + count]})


def _net(cfg):
    net = L.dots3_note_decoder(
        dict(cfg, n_routed_experts=wd.experts_total(cfg)),
        n_layer=cfg["n_layer"], experts_held=wd.experts_held(cfg))
    net.ctx_bucket_floor = 16        # several context branches at toy
    return net


def _reference_logits(cfg, ids, quant=False):
    emb = wd.embeddings(cfg, SEED, F32)
    hid = ref.hidden(cfg, emb, lambda i: wd.layer(cfg, SEED, i, F32),
                     ids, wd.experts_held(cfg), quant=quant,
                     q_block=8)
    return np.asarray(ref.head(hid, emb["norm_f"], emb["lm_head"],
                               cfg["rms_norm_eps"], quant=quant))


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(5).randint(0, 100, (3, 72))


@pytest.fixture(scope="module")
def want(ids):
    return _reference_logits(_share(0, 8), ids)


def _chunked(net, params, cache, seqs, slots, upto):
    """Every sequence's first ``upto[i]`` tokens through
    `forward_chunk`, one one-row chunk of CHUNK a call as the engine
    runs them; the logits after each chunk, by (row, end)."""
    fn = jax.jit(lambda c, i, s, n, at: net.forward_chunk(
        params, c, i, s, n, slots=at))
    out = {}
    for r, (seq, slot, n) in enumerate(zip(seqs, slots, upto)):
        for off in range(0, n, CHUNK):
            m = min(CHUNK, n - off)
            row = np.zeros((1, CHUNK), np.int32)
            row[0, :m] = seq[off:off + m]
            cache, logits = fn(cache, row, np.array([off], np.int32),
                               np.array([m], np.int32),
                               np.array([slot], np.int32))
            out[r, off + m] = np.asarray(logits[0])
    return cache, out


# -- the model against the reference ----------------------------------

def test_chunked_prefill_then_decode_matches_the_reference(ids, want):
    """Prompts of 37, 52 and 21 tokens in chunks of 8 (so past the 16
    keys an indexer keeps and the window of 9, with a ragged last
    chunk), then every further token through `decode_step`, through
    both pools, against the reference's one full pass, on logits."""
    cfg = _share(0, 8)
    net, params = _net(cfg), wd.weights(cfg, SEED, F32)
    assert np.abs(want).max() > 2.0
    plens, slots = [37, 52, 21], [2, 0, 3]
    cache = net.init_kv_cache(4, 80, page_size=4,
                              max_chunk=CHUNK)
    cache, got = _chunked(net, params, cache, ids, slots, plens)
    worst = max(np.abs(v - want[r, end - 1]).max()
                for (r, end), v in got.items())
    step = jax.jit(lambda c, t, a: net.decode_step(params, c, t,
                                                   active=a))
    at = np.zeros(4, np.int64)
    row_of = {s: r for r, s in enumerate(slots)}
    for r, s in enumerate(slots):
        at[s] = plens[r]
    for j in range(18):
        active = np.array([s in row_of and not (s == 3 and j >= 5)
                           for s in range(4)])
        tok = np.array([ids[row_of[s], at[s]] if active[s] else 0
                        for s in range(4)], np.int32)
        cache, logits = step(cache, tok, active)
        for s in range(4):
            if active[s]:
                worst = max(worst, np.abs(
                    logits[s] - want[row_of[s], at[s]]).max())
        at += active
    assert list(np.asarray(cache.seq_lens)) == list(at)
    assert at[1] == 0 and at[3] == 26 and at[0] == 70
    assert worst < LOGIT_TOL, worst


def test_whole_prompt_prefill_is_the_chunked_prefill(ids, want):
    """`prefill` (one program a prompt) and `forward_chunk` leave the
    same logits and the same cache behind: a decode step after
    either reads the same rows."""
    cfg = _share(0, 8)
    net, params = _net(cfg), wd.weights(cfg, SEED, F32)
    padded = np.zeros((2, 64), np.int32)
    plens = np.array([45, 30])
    for r in range(2):
        padded[r, :plens[r]] = ids[r, :plens[r]]
    slots = np.array([3, 1], np.int32)
    c0 = net.init_kv_cache(4, 80, page_size=4,
                           max_chunk=CHUNK)
    whole, logits = jax.jit(net.prefill)(params, c0, padded, plens,
                                         slots)
    c1 = net.init_kv_cache(4, 80, page_size=4,
                           max_chunk=CHUNK)
    parts, got = _chunked(net, params, c1, ids, slots, plens)
    for r in range(2):
        assert np.abs(logits[r] - want[r, plens[r] - 1]).max() < \
            LOGIT_TOL
        assert np.abs(logits[r] - got[r, plens[r]]).max() < LOGIT_TOL
    assert list(whole.seq_lens) == list(parts.seq_lens) == \
        [0, 30, 0, 45]
    tok = np.array([0, ids[1, 30], 0, ids[0, 45]], np.int32)
    step = jax.jit(lambda c: net.decode_step(params, c, tok))
    a, b = step(whole)[1], step(parts)[1]
    assert np.abs(a - b).max() < LOGIT_TOL
    assert np.abs(a[3] - want[0, 45]).max() < LOGIT_TOL


def test_call_is_the_reference_forward(ids, want):
    cfg = _share(0, 8)
    got = _net(cfg).call(wd.weights(cfg, SEED, F32),
                         jnp.asarray(ids[:, :40]))
    assert np.abs(np.asarray(got) - want[:, :40]).max() < LOGIT_TOL


def test_float8_control_fails_the_tolerance(ids, want):
    gap = np.abs(_reference_logits(_share(0, 8), ids[:1], quant=True)
                 - want[:1]).max()
    assert gap > 100 * LOGIT_TOL, gap


@pytest.mark.parametrize("fault", ["selection", "window", "gate"])
def test_a_fault_in_the_new_mechanisms_shows_in_the_logits(
        ids, want, monkeypatch, fault):
    """What each of the three mechanisms is worth at this size: with
    a wrong chosen set (the 16 LOWEST scores), a window off by one or
    the gate dropped, the logits leave the reference by far more than
    the tolerance."""
    cfg = _share(0, 8)
    from analytics_zoo_tpu.pipeline.api.keras.layers import decoder
    if fault == "selection":
        monkeypatch.setattr(
            decoder, "topk_mask",
            lambda s, vis, k: topk_mask(-s, vis, k))
    net, params = _net(cfg), wd.weights(cfg, SEED, F32)
    for att in set(net.attentions):
        if fault == "window" and att.window:
            monkeypatch.setattr(att, "window", att.window - 1)
        if fault == "gate":
            monkeypatch.setattr(att, "gate", False)
    cache = net.init_kv_cache(4, 80, page_size=4,
                              max_chunk=CHUNK)
    _cache, got = _chunked(net, params, cache, ids[:1], [1], [48])
    gap = max(np.abs(v - want[r, end - 1]).max()
              for (r, end), v in got.items())
    assert gap > 50 * LOGIT_TOL, gap


def test_a_wrong_chosen_set_in_a_decode_step_shows_in_the_logits(
        ids, want, monkeypatch):
    """The decode step's own selection (`lax.top_k` over the cached
    index keys' scores, not the chunk's `topk_mask`): sound chunks,
    then a step that keeps the 16 LOWEST scores."""
    cfg = _share(0, 8)
    from analytics_zoo_tpu.pipeline.api.keras.layers import decoder
    scores = decoder.index_scores
    monkeypatch.setattr(
        decoder, "index_scores",
        lambda q, w, k: scores(q, w, k) * (-1 if q.shape[1] == 1
                                           else 1))
    net, params = _net(cfg), wd.weights(cfg, SEED, F32)
    cache = net.init_kv_cache(4, 80, page_size=4, max_chunk=CHUNK)
    cache, got = _chunked(net, params, cache, ids[:1], [1], [40])
    assert max(np.abs(v - want[0, end - 1]).max()
               for (_r, end), v in got.items()) < LOGIT_TOL
    tok = np.zeros(4, np.int32)
    tok[1] = ids[0, 40]
    _cache, logits = jax.jit(lambda c, t, a: net.decode_step(
        params, c, t, active=a))(cache, tok, np.arange(4) == 1)
    gap = np.abs(logits[1] - want[0, 40]).max()
    assert gap > 50 * LOGIT_TOL, gap


def test_routing_is_the_references():
    """Sigmoid scores, the bias in the choice and not in the weights,
    the weights of the chosen summing to one."""
    cfg = _share(0, 16)
    moe = _net(cfg).feed_forward[1]
    p = wd.layer(cfg, SEED, 1, F32)["ffn"]
    assert float(jnp.abs(p["router_bias"]).max()) > 0.01
    x = jax.random.normal(jax.random.key(2), (64, 64), F32)
    experts, weights = moe.route(p, x)
    want_e, want_w = ref.route(cfg, p["router"], p["router_bias"], x)
    assert (np.asarray(experts) == np.asarray(want_e)).all()
    assert np.allclose(np.asarray(weights), np.asarray(want_w),
                       rtol=1e-6)
    assert np.allclose(np.asarray(weights).sum(1), 1.0, rtol=1e-6)
    scores = jax.nn.sigmoid(x @ p["router"])
    plain = jax.lax.top_k(scores, 3)[1]
    assert (np.sort(np.asarray(plain)) !=
            np.sort(np.asarray(experts))).any()     # the bias chooses
    assert np.allclose(
        np.asarray(weights), np.take_along_axis(
            np.asarray(scores), np.asarray(experts), 1) /
        np.take_along_axis(np.asarray(scores), np.asarray(experts),
                           1).sum(1, keepdims=True), rtol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert layer, eight chips with two experts each: the
    routed parts of the eight shares plus the shared expert counted
    once are the uncut reference's layer."""
    whole = _share(0, 16)
    p_all = wd.layer(whole, SEED, 2, F32)
    x = jax.random.normal(jax.random.key(4), (48, 64), F32)
    want = ref._moe(whole, {k: v.astype(F32) for k, v in
                            p_all["ffn"].items()}, x, (0, 16), False)
    shared = ref._swiglu(x, p_all["ffn"]["shared_gate"],
                         p_all["ffn"]["shared_up"],
                         p_all["ffn"]["shared_down"], False)
    total, held_sum = shared, 0
    for chip in range(8):
        cfg = _share(2 * chip, 2)
        p = wd.layer(cfg, SEED, 2, F32)["ffn"]
        assert (p["experts_up"] ==
                p_all["ffn"]["experts_up"][2 * chip:2 * chip + 2]).all()
        y, counts = _net(cfg).feed_forward[2](p, x)
        total = total + (y - shared)
        assert int(counts[0]) == 48 * 3
        held_sum += int(counts[1])
    assert held_sum == 48 * 3          # every assignment lands once
    assert np.abs(np.asarray(total - want)).max() < 1e-5
    assert np.abs(np.asarray(want - shared)).max() > 0.05


# -- the cache of two pools -------------------------------------------

@pytest.mark.parametrize("max_context", [64, 256, 4096])
def test_window_pool_does_not_grow_with_the_context(max_context):
    """Three context layers in the page pool the allocator owns, with
    their index keys beside; three window layers in a ring of
    ceil((9 - 1 + 8) / 4) + 1 = 5 pages a slot, whatever the
    context."""
    net = _net(_share(0, 8))
    cache = net.init_kv_cache(4, max_context, page_size=4,
                              max_chunk=CHUNK)
    pages = max_context // 4
    assert cache.pages.shape == (3, 4 * pages, 4, kvc.ROW_ALIGN)
    assert cache.index.shape == (3, 4 * pages, 4, kvc.ROW_ALIGN)
    assert cache.window.shape == (3, 4 * 5, 4, kvc.ROW_ALIGN)
    assert cache.window_ring == 5
    assert (cache.num_pages, cache.max_context) == (4 * pages,
                                                    max_context)
    # the published sizes: 704 values a token in the context pool,
    # 1088 in the window pool, 513 - 1 + 2048 positions a slot
    big = kvc.init_row_cache(
        3, 8, 32768, 576, dtype=jnp.bfloat16, index_layers=3,
        index_width=128, window_layers=3, window_width=1088,
        window_tokens=512 + 2048)
    assert big.pages.shape[-1] + big.index.shape[-1] == 640 + 128
    assert big.window.shape == (3, 8 * 161, 16, 1152)


def test_a_chunk_the_ring_cannot_take_is_refused():
    net = _net(_share(0, 8))
    cache = net.init_kv_cache(2, 64, page_size=4, max_chunk=4)
    with pytest.raises(ValueError, match="max_chunk"):
        net.forward_chunk(wd.weights(_share(0, 8), SEED, F32), cache,
                          np.zeros((1, 16), np.int32), np.zeros(1),
                          np.ones(1), slots=np.zeros(1))


def test_exact_top_k_breaks_ties_by_index():
    s = jnp.asarray(np.random.RandomState(0).randn(2, 5, 40), F32)
    s = s.at[0, 0, :12].set(9.0).at[1, 2, 5:].set(-3.0)
    vis = jnp.asarray(np.random.RandomState(1).rand(2, 5, 40) > 0.3)
    got = np.asarray(jax.jit(lambda a, b: topk_mask(a, b, 8))(s, vis))
    _, idx = jax.lax.top_k(jnp.where(vis, s, -jnp.inf), 8)
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    want &= np.asarray(vis)
    assert (got == want).all() and (got.sum(-1) == 8).all()
    few = vis.at[0, 1].set(jnp.arange(40) < 3)
    assert np.asarray(topk_mask(s, few, 8))[0, 1].sum() == 3


# -- the engine and the batcher ---------------------------------------

def _engine(cfg, **kw):
    kw = {"max_slots": 3, "max_context": 96, "page_size": 4, **kw}
    return GenerationEngine(_net(cfg), wd.weights(cfg, SEED, F32),
                            **kw)


@pytest.mark.parametrize("kw, ring", [
    ({}, 4), ({"prefill_chunk": CHUNK}, 5), ({"prefill_chunk": 16}, 7)])
def test_engine_sizes_the_window_ring_by_its_own_chunk(kw, ring):
    """The engine hands `init_kv_cache` the most tokens one of its
    chunk programs writes: a window of 9 and the chunk, in pages of
    4, one page more than they fill."""
    eng = _engine(_share(0, 8), **kw)
    assert eng.cache.window_ring == ring
    assert eng.cache.window.shape[1] == eng.max_slots * ring


def _counter(name):
    fam = obs.snapshot().get(name)
    return sum(v["value"] for v in fam["values"]) if fam else 0


def test_engine_chunked_prefill_serves_the_whole_prompt_tokens(ids):
    """The engine's chunked admission (one-row chunks addressed by
    slot, interleaved with decode steps of a resident slot) gives
    the tokens of whole-prompt admission, and counts what the new
    mechanisms did."""
    cfg = _share(0, 8)
    prompts = [ids[0, :50].tolist(), ids[1, :27].tolist()]

    def serve(eng, chunked):
        out = {0: [], 1: []}
        if chunked:
            s0, = eng.admit_partial([(prompts[0], 12, 0.0)])
            s1 = None
            first = {}
            while eng.prefilling_slots:
                for slot, tok in eng.prefill_step():
                    first[slot] = tok
                if s1 is None:      # a second prompt joins mid-way
                    s1, = eng.admit_partial([(prompts[1], 12, 0.0)])
            out[0].append(first[s0])
            out[1].append(first[s1])
        else:
            (s0, t0), (s1, t1) = eng.admit(
                [(p, 12, 0.0) for p in prompts])
            out[0].append(t0)
            out[1].append(t1)
        active = np.zeros(eng.max_slots, bool)
        active[[s0, s1]] = True
        for _ in range(11):
            toks = eng.step(active)
            out[0].append(int(toks[s0]))
            out[1].append(int(toks[s1]))
        return out

    before = {n: _counter(n) for n in L.decoder.ATTENTION_COUNTERS}
    got = serve(_engine(cfg, prefill_chunk=CHUNK), True)
    counted = {n: _counter(n) - before[n] for n in before}
    assert got == serve(_engine(cfg), False)
    vis, kept, read, causal, recycled = counted.values()
    # three full layers; a query at position t sees t + 1 keys and
    # keeps min(t + 1, 16)
    tri = lambda n: n * (n + 1) // 2
    assert vis == 3 * (tri(61) + tri(38))
    assert kept == 3 * (tri(61) - tri(45) + tri(38) - tri(22))
    # three window layers of 9: a query attends min(t + 1, 9) keys of
    # the t + 1 it would attend with no window
    win = lambda n: sum(min(t + 1, 9) for t in range(n))
    assert causal == vis and read == 3 * (win(61) + win(38))
    # three window layers, a ring of 5 pages of 4: the 61 and 38
    # positions begin 16 and 10 pages, all past the fifth recycled
    assert recycled == 3 * ((16 - 5) + (10 - 5))


def test_batcher_serves_long_prompts_through_both_pools(ids):
    cfg = _share(0, 8)
    eng = _engine(cfg, prefill_chunk=CHUNK)
    # the step, the chunk program, and of the three prompt buckets
    # (32, 64, 96) the one a prompt of a single chunk reaches
    assert eng.prompt_buckets == (32, 64, 96) and eng.warm() == 3
    batcher = ContinuousBatcher(eng, max_new_cap=16).start()
    try:
        futs = [batcher.submit(ids[r, :n].tolist(), 10, 0.0)
                for r, n in ((0, 60), (1, 33), (2, 5))]
        got = [f.result(timeout=300) for f in futs]
    finally:
        batcher.stop()
    whole = _engine(cfg)
    for r, n in ((0, 60), (1, 33), (2, 5)):
        (slot, t0), = whole.admit([(ids[r, :n].tolist(), 10, 0.0)])
        toks = [t0]
        active = np.zeros(3, bool)
        active[slot] = True
        for _ in range(9):
            toks.append(int(whole.step(active)[slot]))
        whole.release(slot)
        assert list(got[[0, 1, 2].index(r)]) == toks
    assert eng.free_pages == eng.allocator.max_pages


# -- the chunk attention kernel against the XLA body it sits on -------

def _chunk_case(name):
    """(q, k, v, mask, block_q, block_k, tiles by hand or None) of one
    case at the interpreter's size: 256 queries of 2 heads."""
    rs = np.random.RandomState(11)
    c, h = 256, 2
    rand = lambda *s: jnp.asarray(rs.randn(*s), F32)
    if name in ("topk_behind_an_unfilled_bucket", "bfloat16"):
        # a bucket of 1024 cached keys of which 300 are written, the
        # chunk's own 256 behind them, the 64 of largest score kept
        t_ctx, starts, d = 1024, 300, 192
        q_pos = starts + np.arange(c)
        k_pos = np.concatenate([np.arange(t_ctx), q_pos])
        ok = np.concatenate([np.arange(t_ctx) < starts,
                             np.ones(c, bool)])
        vis = ok[None] & (k_pos[None] <= q_pos[:, None])
        mask = topk_mask(rand(1, c, t_ctx + c), jnp.asarray(vis[None]),
                         64)
        # key blocks of 256: block 0 and the 44 written keys of block
        # 1 for both query blocks; blocks 2 and 3 hold nothing; of
        # the chunk's own block 4 every query sees its half or more
        tiles, bq, bk = [[1, 1, 0, 0, 1]] * 2, 128, 256
    elif name == "window_over_a_ragged_ring":
        # a ring of 400 positions of which the last 100 before the
        # chunk are written, then the chunk: 656 keys, no multiple of
        # 128; a window of 65
        ring, starts, d = 400, 500, 256
        q_pos = starts + np.arange(c)
        k_pos = np.concatenate([starts - 100 + np.arange(ring), q_pos])
        ok = np.concatenate([np.arange(ring) < 100, np.ones(c, bool)])
        back = q_pos[:, None] - k_pos[None]
        mask = jnp.asarray((ok[None] & (back >= 0) & (back < 65))[None])
        # key blocks of 128 over 656 keys padded to 768: the written
        # keys are 0-99 (block 0), of which query block 0 sees the
        # last 64; the chunk's own are 400-655: query block 0 (400-527)
        # sees blocks 3 and 4 (512-527), query block 1 (own keys
        # 528-655, and back to 464) blocks 3, 4 and 5
        tiles, bq, bk = [[1, 0, 0, 1, 1, 0], [0, 0, 0, 1, 1, 1]], \
            128, 128
    else:
        assert name == "a_query_block_with_no_key"
        # rows of padding behind a short chunk: no key at all, and
        # the rows before them see keys 0 to 255 of 384
        d = 192
        t = 384
        m = np.tril(np.ones((c, t), bool), k=t - c)
        m[128:] = False
        mask = jnp.asarray(m[None])
        tiles, bq, bk = [[1, 1, 0], [0, 0, 0]], 128, 128
    t = mask.shape[-1]
    q, k, v = rand(1, c, h, d), rand(1, t, h, d), rand(1, t, h, 128)
    if name == "bfloat16":
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    return q, k, v, mask, bq, bk, np.asarray([tiles])


@pytest.mark.parametrize("case", [
    "topk_behind_an_unfilled_bucket", "window_over_a_ragged_ring",
    "a_query_block_with_no_key", "bfloat16"])
def test_chunk_kernel_is_the_masked_attention_it_replaces(case):
    """`zoo_flash_chunk` under the interpreter against
    `masked_attention`'s XLA body: keys 192 and 256 wide against
    values of 128, any number of keys, the table of occupied tiles as
    counted by hand, the same output with and without the skip, and
    a finite row for a query that sees nothing."""
    from analytics_zoo_tpu.ops import attention as att
    from analytics_zoo_tpu.ops import flash_attention as fa
    q, k, v, mask, bq, bk, tiles = _chunk_case(case)
    np.testing.assert_array_equal(
        np.asarray(fa.mask_tiles(mask, bq, bk)), tiles)
    want = np.asarray(att._masked_attention_xla(
        q, k, v, mask, 0.11).astype(F32))
    run = lambda skip: np.asarray(fa.masked_chunk_attention(
        q, k, v, mask, 0.11, block_q=bq, block_k=bk, skip=skip,
        interpret=True).astype(F32))
    got, dense = run(True), run(False)
    assert got.shape == want.shape == (1, 256, 2, 128)
    assert np.isfinite(got).all() and np.isfinite(dense).all()
    seen = np.asarray(mask.any(-1))[0]
    assert seen.sum() == (128 if "no_key" in case else 256)
    tol = 2e-2 if case == "bfloat16" else 2e-5
    np.testing.assert_allclose(got[0][seen], want[0][seen], atol=tol,
                               rtol=tol)
    # a tile with no 1 adds exp(-1e30 - m) = 0 to a row that has a key
    np.testing.assert_array_equal(got[0][seen], dense[0][seen])


def test_masked_attention_takes_the_kernel_by_what_it_observes(
        monkeypatch):
    from analytics_zoo_tpu.ops import attention as att
    sds = lambda n, w, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        (1, n, 16, w), dt)
    q, k, v = sds(2048, 192), sds(34816, 192), sds(34816, 128)
    assert att.masked_flash_blocks(q, k, v) is None       # the CPU
    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    assert att.masked_flash_blocks(q, k, v) == (512, 1024)
    assert att.masked_flash_blocks(
        sds(2048, 256), sds(4624, 256), sds(4624, 128)) == (512, 1024)
    assert att.masked_flash_blocks(sds(384, 192), sds(600, 192),
                                   sds(600, 128)) == (128, 640)
    # a whole prompt of any length, other dtypes, wider heads
    assert att.masked_flash_blocks(sds(72, 192), k, v) is None
    assert att.masked_flash_blocks(q, k, sds(34816, 128, F32)) is None
    assert att.masked_flash_blocks(
        sds(2048, 192, jnp.int8), sds(128, 192, jnp.int8),
        sds(128, 128, jnp.int8)) is None
    assert att.masked_flash_blocks(sds(2048, 320), sds(128, 320),
                                   v) is None
    monkeypatch.setenv("ZOO_TPU_ATTENTION", "xla")
    assert att.masked_flash_blocks(q, k, v) is None


def test_chunk_programs_count_the_tiles_they_run(monkeypatch):
    """`forward_chunk(..., stats=True)` of 128-token chunks with the
    kernel under the interpreter: the logits of the XLA body, and the
    two tile counters behind the tokens. The second chunk starts
    behind 1000 tokens in a bucket of 2048: of a full layer's three
    key blocks of 1024 the middle one holds no written key."""
    cfg = dict(_share(0, 8), max_position_embeddings=4096)
    net = _net(cfg)
    net.ctx_bucket_floor = 2048
    assert net.step_counters[-2:] == L.decoder.CHUNK_TILE_COUNTERS
    params = wd.weights(cfg, SEED, F32)
    c = 128
    toks = np.random.RandomState(3).randint(0, 100, (1, c))

    def run():
        cache = net.init_kv_cache(1, 4096, page_size=16, dtype=F32,
                                  max_chunk=c)
        fn = jax.jit(lambda cache, s: net.forward_chunk(
            params, cache, toks, s, np.array([c], np.int32),
            stats=True))
        out = []
        for start in (0, 1000):
            _, logits, counts = fn(cache, np.array([start], np.int32))
            out.append((np.asarray(logits), np.asarray(counts)))
        return out

    plain = run()
    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    tiled = run()
    for (want, none), (got, counts) in zip(plain, tiled):
        np.testing.assert_allclose(got, want, atol=LOGIT_TOL,
                                   rtol=LOGIT_TOL)
        assert none[-2:].tolist() == [0, 0]
    # one head block a layer. From 0: one tile a layer, six layers
    assert tiled[0][1][-2:].tolist() == [6, 6]
    # from 1000: three tiles a full layer and two of them run; a
    # sliding layer's ring and chunk are one tile
    assert tiled[1][1][-2:].tolist() == [3 * 3 + 3, 3 * 2 + 3]
    before = {n: _counter(n) for n in L.decoder.CHUNK_TILE_COUNTERS}
    net.record_step_counts(tiled[1][1])
    assert [_counter(n) - before[n] for n in before] == [12, 9]
