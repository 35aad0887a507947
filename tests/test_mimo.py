"""`mimo_v2_flash_decoder` at a toy size against the benchmark's
plain float32 reference (`benchmark/reference/mimo_v2_flash.py`, which
imports nothing of the program): 7 layers in the published order
(full and dense, sliding x 4, full, sliding), hidden 64; 8 query
heads of 24 over 2 (full) or 4 (sliding) K/V heads with values of 16
scaled by 0.707, a rotary on the first 8 of the 24 with a base a
kind, a window of 9 with a learned sink a head; 16 sigmoid-routed
experts, top-3 with a selection bias, no shared expert.

Contexts run to 70 tokens: past the window and the ring (its pages
are written over). Weights are float32 and the CPU multiplies float32
exactly, so what separates the two sides is the order of float32
sums; logits of magnitude 2-7 agree to 2e-4.
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
from analytics_zoo_tpu.ops import attention as att_ops         # noqa: E402
from analytics_zoo_tpu.ops import flash_attention as fa        # noqa: E402
from analytics_zoo_tpu.ops import kv_cache as kvc              # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras import layers as L   # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras.layers import decoder  # noqa: E402
from analytics_zoo_tpu.pipeline.inference.batching import (    # noqa: E402
    ContinuousBatcher)
from analytics_zoo_tpu.pipeline.inference.generation import (  # noqa: E402
    GenerationEngine)
from benchmark import weights_mimo as wm                       # noqa: E402
from benchmark.reference import mimo_v2_flash as ref           # noqa: E402

F32 = jnp.float32
LOGIT_TOL = 2e-4
TOY = dict(
    name="toy", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=7, n_layer=7,
    hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
    moe_layer_freq=[0, 1, 1, 1, 1, 1, 1],
    n_routed_experts=16, n_shared_experts=None,
    num_experts_per_tok=3, routed_scaling_factor=None,
    scoring_func="sigmoid", norm_topk_prob=True,
    topk_method="noaux_tc", n_group=1, topk_group=1,
    num_attention_heads=8, num_key_value_heads=2, head_dim=24,
    v_head_dim=16, swa_num_attention_heads=8,
    swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
    partial_rotary_factor=0.334, rope_theta=5000000,
    swa_rope_theta=10000, sliding_window=9,
    attention_value_scale=0.707, add_swa_attention_sink_bias=True,
    add_full_attention_sink_bias=False, layernorm_epsilon=1e-5,
    vocab_size=100, max_position_embeddings=256,
    initializer_range=0.2,
    init={"router_bias_std": 0.05, "sink_std": 1.0})
SEED = 2 ** 31 + 7
CHUNK = 8


def _share(first, count):
    return dict(TOY, n_routed_experts=count,
                published={"n_routed_experts": 16},
                held={"experts": [first, first + count]})


def _net(cfg, **kw):
    net = L.mimo_v2_flash_decoder(
        dict(cfg, n_routed_experts=wm.experts_total(cfg)),
        n_layer=cfg["n_layer"], experts_held=wm.experts_held(cfg),
        **kw)
    net.ctx_bucket_floor = 16        # several context branches at toy
    return net


def _reference_logits(cfg, ids, quant=False):
    emb = wm.embeddings(cfg, SEED, F32)
    hid = ref.hidden(cfg, emb, lambda i: wm.layer(cfg, SEED, i, F32),
                     ids, wm.experts_held(cfg), quant=quant,
                     q_block=8)
    return np.asarray(ref.head(hid, emb["norm_f"], emb["lm_head"],
                               cfg["layernorm_epsilon"], quant=quant))


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(5).randint(0, 100, (3, 72))


@pytest.fixture(scope="module")
def want(ids):
    return _reference_logits(_share(0, 8), ids)


def _chunked(net, params, cache, seqs, slots, upto, chunk=CHUNK):
    """Every sequence's first ``upto[i]`` tokens through
    `forward_chunk`, one one-row chunk a call as the engine runs
    them; the logits after each chunk, by (row, end)."""
    fn = jax.jit(lambda c, i, s, n, at: net.forward_chunk(
        params, c, i, s, n, slots=at))
    out = {}
    for r, (seq, slot, n) in enumerate(zip(seqs, slots, upto)):
        for off in range(0, n, chunk):
            m = min(chunk, n - off)
            row = np.zeros((1, chunk), np.int32)
            row[0, :m] = seq[off:off + m]
            cache, logits = fn(cache, row, np.array([off], np.int32),
                               np.array([m], np.int32),
                               np.array([slot], np.int32))
            out[r, off + m] = np.asarray(logits[0])
    return cache, out


# -- the parts against the reference ----------------------------------

def _part_case(full, t=40, seed=3):
    cfg = _share(0, 8)
    net = _net(cfg)
    layer = 0 if full else 1
    part = net.attentions[layer]
    p = wm.layer(cfg, SEED, layer, F32)["attn"]
    x = jax.random.normal(jax.random.key(seed), (2, t, 64), F32)
    want = jnp.stack([ref.attention(cfg, p, row, full, q_block=8)
                      for row in x])
    return cfg, part, p, x, np.asarray(want)


def _part_out(part, p, x):
    a, t, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None],
                           (a, t))
    out, rows = part.chunk(p, x, pos, jnp.ones((a, t), bool))
    assert rows["row"].shape == (a, t, part.row_width)
    return np.asarray(out)


@pytest.mark.parametrize("full", [True, False])
@pytest.mark.parametrize("t", [6, 9, 40])
def test_a_part_is_the_references_attention(full, t):
    """Grouped heads, keys of 24 and values of 16, the rotary on 8 of
    24, the value scale and, on the sliding kind, the window's edge
    and the sink: a chunk shorter than the window, one of just a
    window, and a banded one."""
    _cfg, part, p, x, want = _part_case(full, t)
    assert (part.n_kv, part.rep) == ((2, 4) if full else (4, 2))
    assert part.row_width == part.n_kv * (24 + 16)
    assert part.rope.dim == 8
    assert np.abs(_part_out(part, p, x) - want).max() < 2e-5
    if full:
        out, rows = part.prefill(p, x)
        assert np.abs(np.asarray(out) - want).max() < 2e-5
        assert rows.shape == (2, t, part.row_width)


FAULTS = {
    "window": lambda a: setattr(a, "window", a.window - 1),
    "window_wide": lambda a: setattr(a, "window", a.window + 1),
    "sink": lambda a: setattr(a, "sink", False),
    "value_scale": lambda a: setattr(a, "value_scale", 1.0),
    "rotary_all": lambda a: setattr(a, "rope", L.YarnRope(
        a.k_dim, theta=a.rope.theta)),
    "rope_base": lambda a: setattr(a, "rope", L.YarnRope(
        a.rope.dim, theta=a.rope.theta * 3)),
    "grouping": lambda a: setattr(a, "_project", _interleaved(a)),
}


def _interleaved(part):
    """Query head j on K/V head ``j % G``, not ``j // (H / G)``."""
    project = part._project

    def wrong(p, x, positions):
        q, row = project(p, x, positions)
        return jnp.swapaxes(q.reshape(
            q.shape[:-3] + (part.rep, part.n_kv, part.k_dim)),
            -3, -2), row
    return wrong


@pytest.mark.parametrize("fault, full", [
    ("window", False), ("window_wide", False), ("sink", False),
    ("value_scale", False), ("value_scale", True),
    ("rotary_all", False), ("rotary_all", True),
    ("rope_base", True), ("rope_base", False),
    ("grouping", True), ("grouping", False)])
def test_a_fault_in_a_part_shows(fault, full):
    _cfg, part, p, x, want = _part_case(full)
    FAULTS[fault](part)
    assert np.abs(_part_out(part, p, x) - want).max() > 1e-2


@pytest.mark.parametrize("c, window", [(16, 4), (24, 8), (8, 8),
                                       (32, 5)])
def test_banded_attention_is_the_masked_product(c, window):
    """The band multiplies 2 x block keys a query block and gives what
    the masked product over every key gives."""
    rs = np.random.RandomState(c + window)
    b = 8 if window > 4 else 4
    a, g, r, d, dv = 2, 2, 3, 8, 4
    q = jnp.asarray(rs.randn(a, c, g, r, d), F32)
    k = jnp.asarray(rs.randn(a, b + c, g, d), F32)
    v = jnp.asarray(rs.randn(a, b + c, g, dv), F32)
    start = np.array([[20], [3]])
    k_pos = jnp.asarray(start - b + np.arange(b + c)[None])
    q_pos = k_pos[:, b:]
    k_ok = k_pos >= 0
    sink = jnp.asarray(rs.randn(g, r), F32)
    got = att_ops.banded_attention(q, k, v, q_pos, k_pos, k_ok,
                                   window, 0.3, sink=sink)
    back = q_pos[:, :, None] - k_pos[:, None, :]
    mask = k_ok[:, None] & (back >= 0) & (back < window)
    want = att_ops.grouped_attention(q, k, v, mask, 0.3, sink=sink)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    plain = att_ops.grouped_attention(q, k, v, mask, 0.3)
    assert np.abs(np.asarray(plain - want)).max() > 1e-3


def test_a_band_narrower_than_the_window_is_refused():
    z = jnp.zeros
    with pytest.raises(ValueError, match="band"):
        att_ops.banded_attention(
            z((1, 8, 1, 1, 4)), z((1, 12, 1, 4)), z((1, 12, 1, 4)),
            z((1, 8), jnp.int32), z((1, 12), jnp.int32),
            z((1, 12), bool), 8, 1.0)


# -- the model against the reference ----------------------------------

def test_chunked_prefill_then_decode_matches_the_reference(ids, want):
    """Prompts of 37, 52 and 21 tokens in chunks of 8 (past the
    window of 9, with a ragged last chunk), then every further token
    through `decode_step`, through both pools, against the
    reference's one full pass, on logits."""
    cfg = _share(0, 8)
    net, params = _net(cfg), wm.weights(cfg, SEED, F32)
    assert np.abs(want).max() > 2.0
    plens, slots = [37, 52, 21], [2, 0, 3]
    cache = net.init_kv_cache(4, 80, page_size=4, max_chunk=CHUNK)
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


def test_whole_prompt_prefill_then_decode_matches_the_reference(
        ids, want):
    """`prefill` (one program a prompt, the banded product on the
    sliding layers) and `forward_chunk` leave the same logits and the
    same cache behind; decode steps after either follow the
    reference."""
    cfg = _share(0, 8)
    net, params = _net(cfg), wm.weights(cfg, SEED, F32)
    padded = np.zeros((2, 64), np.int32)
    plens = np.array([45, 30])
    for r in range(2):
        padded[r, :plens[r]] = ids[r, :plens[r]]
    slots = np.array([3, 1], np.int32)
    c0 = net.init_kv_cache(4, 80, page_size=4, max_chunk=CHUNK)
    whole, logits = jax.jit(net.prefill)(params, c0, padded, plens,
                                         slots)
    c1 = net.init_kv_cache(4, 80, page_size=4, max_chunk=CHUNK)
    parts, got = _chunked(net, params, c1, ids, slots, plens)
    for r in range(2):
        assert np.abs(logits[r] - want[r, plens[r] - 1]).max() < \
            LOGIT_TOL
        assert np.abs(logits[r] - got[r, plens[r]]).max() < LOGIT_TOL
    assert list(whole.seq_lens) == list(parts.seq_lens) == \
        [0, 30, 0, 45]
    step = jax.jit(lambda c, t: net.decode_step(params, c, t))
    at = [0, 30, 0, 45]
    for j in range(12):
        tok = np.array([0, ids[1, at[1]], 0, ids[0, at[3]]], np.int32)
        whole, a = step(whole, tok)
        parts, b = step(parts, tok)
        assert np.abs(a - b).max() < LOGIT_TOL
        assert np.abs(a[3] - want[0, at[3]]).max() < LOGIT_TOL
        assert np.abs(a[1] - want[1, at[1]]).max() < LOGIT_TOL
        at[1] += 1
        at[3] += 1


def test_call_is_the_reference_forward(ids, want):
    cfg = _share(0, 8)
    got = _net(cfg).call(wm.weights(cfg, SEED, F32),
                         jnp.asarray(ids[:, :40]))
    assert np.abs(np.asarray(got) - want[:, :40]).max() < LOGIT_TOL


def test_float8_control_fails_the_tolerance(ids, want):
    gap = np.abs(_reference_logits(_share(0, 8), ids[:1], quant=True)
                 - want[:1]).max()
    assert gap > 100 * LOGIT_TOL, gap


def test_slots_of_very_different_lengths_share_a_step(ids, want):
    """One decode step over a slot at 68 positions (17 pages, the
    ring turned over), one at 3 (inside its first page, shorter than
    the window), one idle and one that holds nothing."""
    cfg = _share(0, 8)
    net, params = _net(cfg), wm.weights(cfg, SEED, F32)
    cache = net.init_kv_cache(4, 80, page_size=4, max_chunk=CHUNK)
    cache, _ = _chunked(net, params, cache, ids[:2], [2, 0], [68, 3])
    assert list(np.asarray(cache.seq_lens)) == [3, 0, 68, 0]
    tok = np.array([ids[1, 3], 7, ids[0, 68], 0], np.int32)
    active = np.array([True, False, True, False])
    cache, logits = jax.jit(lambda c: net.decode_step(
        params, c, tok, active=active))(cache)
    assert np.abs(logits[0] - want[1, 3]).max() < LOGIT_TOL
    assert np.abs(logits[2] - want[0, 68]).max() < LOGIT_TOL
    assert np.isfinite(np.asarray(logits)).all()
    assert list(np.asarray(cache.seq_lens)) == [4, 0, 69, 0]


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_ring_wraps_past_window_plus_chunk(ids, want, chunk):
    """A ring of ceil((9 - 1 + chunk) / 4) + 1 pages a slot: 70
    positions turn it over several times, in chunks and in steps, and
    every logit still follows the reference."""
    cfg = _share(0, 8)
    net, params = _net(cfg), wm.weights(cfg, SEED, F32)
    cache = net.init_kv_cache(2, 80, page_size=4, max_chunk=chunk)
    assert cache.window_ring == (8 + chunk + 3) // 4 + 1
    cache, got = _chunked(net, params, cache, ids[:1], [1], [50],
                          chunk=chunk)
    assert max(np.abs(v - want[0, end - 1]).max()
               for (_r, end), v in got.items()) < LOGIT_TOL
    step = jax.jit(lambda c, t: net.decode_step(params, c, t))
    for at in range(50, 70):
        cache, logits = step(cache, np.array([0, ids[0, at]],
                                             np.int32))
        assert np.abs(logits[1] - want[0, at]).max() < LOGIT_TOL


@pytest.mark.parametrize("fault", ["window", "sink", "value_scale",
                                   "rotary_all", "grouping"])
@pytest.mark.parametrize("path", ["chunks", "steps"])
def test_a_fault_shows_in_the_logits(ids, want, fault, path):
    """What each mechanism is worth through the cache: sound chunks,
    then chunks or steps with the fault."""
    cfg = _share(0, 8)
    net, params = _net(cfg), wm.weights(cfg, SEED, F32)
    cache = net.init_kv_cache(2, 80, page_size=4, max_chunk=CHUNK)
    cache, _ = _chunked(net, params, cache, ids[:1], [1], [24])
    for att in set(net.attentions):
        if fault in ("window", "sink") and not att.window:
            continue
        FAULTS[fault](att)
    if path == "chunks":
        fn = jax.jit(lambda c, i: net.forward_chunk(
            params, c, i, np.array([24]), np.array([8]),
            slots=np.array([1])))
        _c, logits = fn(cache, ids[:1, 24:32])
        gap = np.abs(logits[0] - want[0, 31]).max()
    else:
        _c, logits = jax.jit(lambda c: net.decode_step(
            params, c, np.array([0, ids[0, 24]], np.int32)))(cache)
        gap = np.abs(logits[1] - want[0, 24]).max()
    assert gap > 50 * LOGIT_TOL, (fault, path, gap)


# -- the share --------------------------------------------------------

def test_routing_is_the_references():
    cfg = _share(0, 16)
    moe = _net(cfg).feed_forward[1]
    p = wm.layer(cfg, SEED, 1, F32)["ffn"]
    assert "shared_gate" not in p and moe.n_shared == 0
    assert moe.routed_scaling == 1.0
    assert float(jnp.abs(p["router_bias"]).max()) > 0.01
    x = jax.random.normal(jax.random.key(2), (64, 64), F32)
    experts, weights = moe.route(p, x)
    want_e, want_w = ref.route(cfg, p["router"], p["router_bias"], x)
    assert (np.asarray(experts) == np.asarray(want_e)).all()
    assert np.allclose(np.asarray(weights), np.asarray(want_w),
                       rtol=1e-6)
    assert np.allclose(np.asarray(weights).sum(1), 1.0, rtol=1e-6)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One expert layer, sixteen chips with one expert each: the
    routed parts of the sixteen shares are the uncut reference's
    layer (there is no shared expert to count once)."""
    whole = _share(0, 16)
    p_all = wm.layer(whole, SEED, 2, F32)
    x = jax.random.normal(jax.random.key(4), (48, 64), F32)
    want = ref.moe(whole, {k: v.astype(F32) for k, v in
                           p_all["ffn"].items()}, x, (0, 16))
    total, held_sum = 0.0, 0
    for chip in range(16):
        cfg = _share(chip, 1)
        p = wm.layer(cfg, SEED, 2, F32)["ffn"]
        assert (p["experts_up"] ==
                p_all["ffn"]["experts_up"][chip:chip + 1]).all()
        y, counts = _net(cfg).feed_forward[2](p, x)
        total = total + y
        assert int(counts[0]) == 48 * 3
        held_sum += int(counts[1])
    assert held_sum == 48 * 3          # every assignment lands once
    assert np.abs(np.asarray(total - want)).max() < 1e-5
    assert np.abs(np.asarray(want)).max() > 0.05


@pytest.mark.parametrize("first", [0, 4, 8])
def test_a_share_is_the_references_share(ids, first):
    """Another chip's experts: program and reference leave out the
    same part."""
    cfg = _share(first, 4)
    got = _net(cfg).call(wm.weights(cfg, SEED, F32),
                         jnp.asarray(ids[:1, :24]))
    want = _reference_logits(cfg, ids[:1, :24])
    assert np.abs(np.asarray(got) - want).max() < LOGIT_TOL
    other = _reference_logits(_share((first + 4) % 12, 4),
                              ids[:1, :24])
    assert np.abs(other - want).max() > 50 * LOGIT_TOL


# -- the config's keys ------------------------------------------------

def test_the_builder_reads_lists_and_nulls():
    """``hybrid_layer_pattern`` and ``moe_layer_freq`` as lists,
    ``n_shared_experts`` and ``routed_scaling_factor`` as null."""
    net = _net(_share(0, 8))
    kinds = [(a.kind, a.n_kv, a.sink, a.rope.theta)
             for a in net.attentions]
    assert kinds[0] == kinds[5] == ("context", 2, False, 5e6)
    assert all(k == ("window", 4, True, 1e4)
               for i, k in enumerate(kinds) if i not in (0, 5))
    assert [type(f).__name__ for f in net.feed_forward] == \
        ["GatedMLP"] + ["GroupLimitedMoE"] * 6
    assert net._pool_layers == {"context": 2, "window": 5, "index": 0}
    assert net.step_counters[-1] == \
        "zoo_tpu_window_pages_recycled_total"
    flipped = dict(_share(0, 8),
                   moe_layer_freq=[1, 0, 1, 0, 1, 0, 1])
    assert [type(f).__name__ for f in _net(flipped).feed_forward] == \
        ["GroupLimitedMoE", "GatedMLP"] * 3 + ["GroupLimitedMoE"]


@pytest.mark.parametrize("freq, first_dense, want", [
    (1, 1, "DMMMMM"), (2, 0, "MDMDMD"), ([0, 1, 1, 0, 1, 1], 0,
                                          "DMMDMM"),
    ([1, 1, 1, 1, 1, 1], 2, "DDMMMM"), (None, None, "MMMMMM")])
def test_one_feed_forward_helper_reads_every_form(freq, first_dense,
                                                   want):
    cfg = dict(TOY, moe_layer_freq=freq,
               first_k_dense_replace=first_dense)
    ffn = decoder._feed_forward(cfg, None)
    got = "".join("D" if type(ffn(i)).__name__ == "GatedMLP" else "M"
                  for i in range(6))
    assert got == want


def test_heads_that_do_not_divide_are_refused():
    with pytest.raises(ValueError, match="divide"):
        L.GroupedQueryAttention(64, 8, 3, 24, 16, L.YarnRope(8))
    with pytest.raises(ValueError, match="rotary"):
        L.GroupedQueryAttention(64, 8, 2, 24, 16, L.YarnRope(32))


# -- the cache of two geometries --------------------------------------

@pytest.mark.parametrize("max_context", [64, 256, 4096])
def test_two_row_geometries_under_one_table(max_context):
    """Two context layers of 2 x (24 + 16) values a token in the page
    pool the allocator owns, five window layers of 4 x (24 + 16) in a
    ring of ceil((9 - 1 + 8) / 4) + 1 = 5 pages a slot whatever the
    context; no index pool."""
    net = _net(_share(0, 8))
    cache = net.init_kv_cache(4, max_context, page_size=4,
                              max_chunk=CHUNK)
    pages = max_context // 4
    assert cache.pages.shape == (2, 4 * pages, 4, 128)
    assert cache.window.shape == (5, 4 * 5, 4, 256)
    assert cache.index is None and cache.window_ring == 5
    assert isinstance(cache, kvc.RowPagedCache)
    # the published sizes: 1280 values a token in the context pool,
    # 2560 in the ring of 127 + 2048 positions a slot
    big = jax.eval_shape(lambda: kvc.init_row_cache(
        2, 16, 32768, 1280, dtype=jnp.bfloat16, window_layers=5,
        window_width=2560, window_tokens=127 + 2048))
    assert big.pages.shape == (2, 32768, 16, 1280)
    assert big.window.shape == (5, 16 * 137, 16, 2560)


def test_window_table_lists_the_pages_of_the_window():
    cache = kvc.init_row_cache(
        1, 3, 64, 8, page_size=4, window_layers=1, window_width=8,
        window_tokens=16)._replace(
            seq_lens=jnp.asarray([0, 5, 43], jnp.int32))
    ring = cache.window_ring
    table, lens, first = kvc.window_table(cache, 9)
    assert table.shape == (3, 3)
    # slot 2: cached positions 35..42 are the window's: pages 8..10
    assert list(np.asarray(table[2])) == [2 * ring + p % ring
                                          for p in (8, 9, 10)]
    assert (int(lens[2]), int(first[2])) == (43 - 32, 35 - 32)
    assert (int(lens[1]), int(first[1])) == (5, 0)
    assert (int(lens[0]), int(first[0])) == (0, 0)


def test_window_rows_reads_the_ring_row_by_row():
    cache = kvc.init_row_cache(
        1, 2, 64, 8, page_size=4, window_layers=2, window_width=8,
        window_tokens=12)
    pos = jnp.arange(10, 30, dtype=jnp.int32)[None]
    rows = jnp.broadcast_to(
        pos[..., None].astype(F32), (2, 1, 20, 8)) * jnp.asarray(
            [1.0, -1.0])[:, None, None, None]
    turn = (cache.window_ring - 1) * 4
    cache = cache._replace(window=kvc.write_window_rows(
        cache, jnp.asarray([1]), pos, pos >= 30 - turn, rows))
    got = kvc.window_rows(cache, 1, jnp.asarray([1]),
                          jnp.asarray([[29, 20, 18, -3]]))
    # one turn of the ring (12 positions) back, and no further
    assert list(np.asarray(got[0, :3, 0])) == [-29.0, -20.0, -18.0]


# -- the paged kernel for grouped heads -------------------------------

def _gqa_pool(rs, layers, pages, page, g, dk, dv, dtype):
    w = -(-g * (dk + dv) // 128) * 128
    return jnp.asarray(rs.randn(layers, pages, page, w), dtype)


@pytest.mark.parametrize("dtype, page, tol", [
    (jnp.float32, 8, 2e-5), (jnp.bfloat16, 16, 3e-2)])
@pytest.mark.parametrize("window", [0, 40])
def test_paged_gqa_kernel_is_the_gathered_view(dtype, page, tol,
                                               window, monkeypatch):
    """`zoo_paged_gqa_decode` under the interpreter against the
    gathered view, both through `gqa_decode_attention`: 2 K/V heads
    of 64 + 64 under 6 query heads, slots of 0, 1, 150 and 300
    positions over a shuffled table, with a lower edge and a sink
    (the sliding kind) and without."""
    rs = np.random.RandomState(page + window)
    s, g, r, dk, dv = 4, 2, 3, 64, 64
    n_pages = 320 // page
    pool = _gqa_pool(rs, 2, s * n_pages, page, g, dk, dv, dtype)
    table = jnp.asarray(rs.permutation(s * n_pages).reshape(
        s, n_pages), jnp.int32)
    lens = jnp.asarray([0, 1, 150, 300], jnp.int32)
    first = jnp.maximum(lens - window, 0) if window \
        else jnp.zeros_like(lens)
    q = jnp.asarray(rs.randn(s, g, r, dk), dtype)
    new = jnp.asarray(rs.randn(s, pool.shape[-1]), dtype)
    writes = jnp.asarray([False, True, True, True])
    sink = jnp.asarray(rs.randn(g, r), F32) if window else None
    run = lambda impl: att_ops.gqa_decode_attention(
        q, new, pool, 1, table, lens, first, writes, v_dim=dv,
        scale=dk ** -0.5, sink=sink, impl=impl)
    want = run("xla")
    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    before = fa.invocations
    got = jax.jit(lambda: run(None))()
    assert fa.invocations == before + 1
    assert np.abs(np.asarray(got, np.float32) -
                  np.asarray(want, np.float32)).max() < tol
    assert (np.asarray(got[0], np.float32) == 0).all()


def test_paged_gqa_kernel_refuses_rows_that_are_not_lane_tiles():
    assert fa.paged_gqa_supported(16, jnp.bfloat16, 1280, 768, 512)
    assert fa.paged_gqa_supported(16, jnp.bfloat16, 2560, 1536, 1024)
    assert not fa.paged_gqa_supported(16, jnp.bfloat16, 128, 48, 32)
    assert not fa.paged_gqa_supported(4, jnp.bfloat16, 1280, 768, 512)
    with pytest.raises(ValueError, match="paged_gqa_supported"):
        fa.paged_gqa_decode_partial(
            jnp.zeros((1, 2, 2, 24)), jnp.zeros((1, 4, 4, 128)),
            jnp.zeros((1, 4), jnp.int32), jnp.zeros(1, jnp.int32),
            jnp.zeros(1, jnp.int32), 0, k_dim=24, v_dim=16, scale=1.0)


def test_a_step_takes_the_kernel_where_the_rows_allow(monkeypatch):
    """A decoder whose rows fill lane tiles (2 x (64 + 64) and 4 x
    (32 + 32)) under the interpreter: the step's seven layers each
    call the kernel once and give the gathered view's logits."""
    cfg = dict(_share(0, 8), head_dim=64, v_head_dim=64,
               swa_head_dim=32, swa_v_head_dim=32)
    params = wm.weights(cfg, SEED, F32)
    toks = np.random.RandomState(2).randint(0, 100, (2, 30))

    def logits(**kw):
        net = _net(cfg, **kw)
        cache = net.init_kv_cache(2, 64, page_size=8, max_chunk=CHUNK)
        cache, _ = _chunked(net, params, cache, toks, [1, 0], [29, 11])
        return np.asarray(jax.jit(lambda c: net.decode_step(
            params, c, np.array([toks[1, 11], toks[0, 29]]))[1])(
                cache))

    want = logits(attention_impl="xla")
    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    before = fa.invocations
    got = logits()
    assert fa.invocations - before == 7
    assert np.abs(got - want).max() < LOGIT_TOL


# -- the engine and the batcher ---------------------------------------

def _engine(cfg, **kw):
    kw = {"max_slots": 3, "max_context": 96, "page_size": 4, **kw}
    return GenerationEngine(_net(cfg), wm.weights(cfg, SEED, F32),
                            **kw)


def _counter(name):
    fam = obs.snapshot().get(name)
    return sum(v["value"] for v in fam["values"]) if fam else 0


COUNTERS = ("zoo_tpu_moe_assignments_total",
            "zoo_tpu_window_pages_recycled_total")


def test_whole_prompt_prefill_counts_like_chunks(ids):
    """A prompt admitted whole and the same prompt admitted in
    chunks add the same to the experts' and the ring's counters."""
    cfg = _share(0, 8)
    prompt = ids[0, :40].tolist()
    read = lambda: {n: _counter(n) for n in COUNTERS}

    eng = _engine(cfg, prefill_chunk=CHUNK)
    c0 = read()
    (slot, t_whole), = eng.admit([(prompt, 4, 0.0)])
    whole = {n: v - c0[n] for n, v in read().items()}
    eng.release(slot)
    c1 = read()
    eng.admit_partial([(prompt, 4, 0.0)])
    first = {}
    while eng.prefilling_slots:
        first.update(dict(eng.prefill_step()))
    chunks = {n: v - c1[n] for n, v in read().items()}
    assert whole == chunks
    # six expert layers x 3 a token; five window layers whose ring of
    # 5 pages of 4 the prompt's 10 pages turn over
    assert whole == {COUNTERS[0]: 40 * 3 * 6, COUNTERS[1]: 5 * 5}
    assert list(first.values()) == [t_whole]


def test_batcher_serves_short_and_long_prompts_in_one_queue(ids):
    """Prompts of 5 and 8 tokens take the one-row bucket ladder,
    prompts of 60 and 33 `admit_partial` and chunks, interleaved
    with the steps of whoever is resident; each gets the tokens it
    gets alone."""
    cfg = _share(0, 8)
    eng = _engine(cfg, prefill_chunk=CHUNK)
    assert eng.prompt_buckets == (32, 64, 96) and eng.warm() == 3
    batcher = ContinuousBatcher(eng, max_new_cap=16).start()
    sizes = ((0, 60), (2, 5), (1, 33), (2, 8))
    try:
        futs = [batcher.submit(ids[r, :n].tolist(), 10, 0.0)
                for r, n in sizes]
        got = [f.result(timeout=300) for f in futs]
    finally:
        batcher.stop()
    whole = _engine(cfg)
    for i, (r, n) in enumerate(sizes):
        (slot, t0), = whole.admit([(ids[r, :n].tolist(), 10, 0.0)])
        toks = [t0]
        active = np.zeros(3, bool)
        active[slot] = True
        for _ in range(9):
            toks.append(int(whole.step(active)[slot]))
        whole.release(slot)
        assert list(got[i]) == toks
    assert eng.free_pages == eng.allocator.max_pages


# -- the configuration and the cell's files ---------------------------

def _bench_config():
    from benchmark import harness
    return harness.load_named(harness.BENCH_DIR, "configs",
                              "mimo-v2-flash-ep16")


# the catalog row's numbers (model-configs guide,
# `architectures.jsonl`, MiMo-V2-Flash): every width as published
PUBLISHED = dict(
    hidden_size=4096, intermediate_size=16384,
    moe_intermediate_size=2048, num_attention_heads=64,
    num_key_value_heads=4, head_dim=192, v_head_dim=128,
    swa_num_attention_heads=64, swa_num_key_value_heads=8,
    swa_head_dim=192, swa_v_head_dim=128, num_hidden_layers=48,
    num_experts_per_tok=8, sliding_window=128,
    sliding_window_size=128, attention_chunk_size=128,
    partial_rotary_factor=0.334, rope_theta=5000000,
    swa_rope_theta=10000, attention_value_scale=0.707,
    layernorm_epsilon=1e-5, max_position_embeddings=262144,
    n_group=1, topk_group=1)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_the_published_number(key):
    assert _bench_config()[key] == PUBLISHED[key]


def test_configuration_states_its_cut():
    cfg = _bench_config()
    assert cfg["reduced"] == ["n_layer", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["n_layer"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 16, 19072)
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 256,
                                "vocab_size": 152576}
    assert cfg["held"]["experts"] == [0, 16]
    assert cfg["n_shared_experts"] is None and \
        cfg["routed_scaling_factor"] is None
    assert len(cfg["hybrid_layer_pattern"]) == 48 == \
        len(cfg["moe_layer_freq"])
    assert cfg["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    assert cfg["hybrid_layer_pattern"].count(0) == 9
    for key in ("sink", "attention_value_scale", "sliding_window",
                "attention_chunk_size", "partial_rotary_factor",
                "router_bias", "weights", "partial_sum"):
        assert key in cfg["assumed"], key
    assert abs(cfg["init"]["residual_out_scale"] -
               (2 * 48) ** -0.5) < 1e-4


def test_flops_count_the_tree_the_builder_builds():
    """`flops_mimo.params` to the element, 3430.0 M, and the bytes of
    a bfloat16 tree whose selection and sink biases stay float32."""
    from benchmark import flops_mimo as fm
    from benchmark.drivers.generate_mimo import make_net
    cfg = _bench_config()
    net = make_net(cfg)
    tree = jax.eval_shape(lambda: net.build(jax.random.key(0), (16,)))
    leaves = jax.tree_util.tree_leaves(tree)
    p = fm.params(cfg)
    assert p["total"] == sum(int(np.prod(a.shape)) for a in leaves)
    assert abs(p["total"] - 3430.0e6) < 0.05e6
    assert abs(fm.param_bytes(cfg, 2) - 6.86e9) < 0.005e9
    made = jax.eval_shape(lambda: wm.weights(cfg, 3, jnp.bfloat16))
    assert fm.param_bytes(cfg, 2) == sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(made))
    assert jax.tree_util.tree_structure(made) == \
        jax.tree_util.tree_structure(tree)
    cache = jax.eval_shape(lambda: net.init_kv_cache(
        16, 32768, page_size=16, dtype=jnp.bfloat16, max_chunk=2048))
    pool = int(np.prod(cache.pages.shape)) * 2
    ring = int(np.prod(cache.window.shape)) * 2
    assert abs(pool - 2.68e9) < 0.01e9 and abs(ring - 0.90e9) < 0.01e9


def test_the_cells_files_pass_the_selfcheck():
    from benchmark import selfcheck
    cells = selfcheck.check_data_files()
    assert "mimo-generate-mixed16" in cells
    selfcheck.check_manifest(cells)
    cell = cells["mimo-generate-mixed16"]
    assert cell["cell"]["driver"] == cell["traffic"]["driver"] == \
        "generate_mimo" and cell["cell"]["chips"] == 1
    mix = cell["traffic"]
    assert (mix["clients"], mix["pool"], mix["max_new_cap"],
            mix["check_requests"], mix["trace_seconds"]) == \
        (16, 32, 512, 4, 4)
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 1.3, "min": 64,
        "max": 30720}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 160, "sigma": 0.6, "min": 32,
        "max": 512}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference",
        "mimo_v2_flash.py")
    assert "analytics_zoo_tpu" not in open(path).read()
