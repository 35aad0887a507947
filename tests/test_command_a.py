"""`command_a_decoder` at a toy size against the benchmark's plain
float32 reference (`benchmark/reference/command_a.py`, which imports
nothing of the program): layers ``S S S F`` of hidden 64, each ONE
parallel block behind a bias-free LayerNorm; attention of 8 query
heads over 2 K/V heads of 16, the sliding layers with an interleaved
(GPT-J) rotary and a window of 8, the full layer with no positional
encoding; 8 sigmoid-routed SwiGLU experts, top-2, 4 held, and 2
shared experts averaged with them; a head tied to the embedding, 128
rows of a vocabulary of 256.

Weights are float32 and the CPU multiplies float32 exactly, so what
separates the two sides is the order of float32 sums; logits of
magnitude 2-6 agree to 2e-4.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from analytics_zoo_tpu.pipeline.api.keras import layers as L   # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras.layers.moe import (  # noqa: E402
    GroupLimitedMoE, gated_mlp)
from benchmark import flops_command as fc                      # noqa: E402
from benchmark import harness                                  # noqa: E402
from benchmark import weights_command as wc                    # noqa: E402
from benchmark.reference import command_a as ref               # noqa: E402

F32 = jnp.float32
LOGIT_TOL = 2e-4
WINDOW = 8
TOY = dict(
    name="toy", hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, sliding_window=WINDOW,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    num_hidden_layers=4, n_layer=4, rope_theta=50000,
    rope_parameters={"rope_theta": 50000, "rope_type": "default"},
    rotary_pct=1, position_embedding_type="rope_gptj",
    use_parallel_block=True, layer_norm_eps=1e-5, logit_scale=1,
    tie_word_embeddings=True, num_experts=8, n_routed_experts=8,
    num_experts_per_tok=2, num_shared_experts=2, intermediate_size=32,
    norm_topk_prob=True, expert_selection_fn="sigmoid",
    shared_expert_combination_strategy="average",
    first_k_dense_replace=0, attention_bias=False, use_qk_norm=False,
    hidden_act="silu", use_gated_activation=True, vocab_size=128,
    max_position_embeddings=256, initializer_range=0.2,
    init={"embed_std": 0.25})
SEED = 2 ** 31 + 43
CHUNK = 12                         # longer than the window of 8


def _share(first, count, **kw):
    return dict(TOY, n_routed_experts=count,
                published={"n_routed_experts": 8, "vocab_size": 256},
                held={"experts": [first, first + count]}, **kw)


def _net(cfg, **kw):
    net = L.command_a_decoder(
        cfg, n_layer=cfg["n_layer"], experts_held=wc.experts_held(cfg),
        **kw)
    net.ctx_bucket_floor = 16        # several context branches at toy
    return net


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(5).randint(0, 128, (3, 48))


@pytest.fixture(scope="module")
def model():
    cfg = _share(0, 4)
    return cfg, _net(cfg), wc.weights(cfg, SEED, F32)


@pytest.fixture(scope="module")
def want(ids, model):
    cfg, _net_, params = model
    hid = ref.hidden(cfg, params, lambda i: params["layers"][i], ids,
                     wc.experts_held(cfg), q_block=8)
    return np.asarray(ref.head(hid, params["norm_f"],
                               params["tok_embed"],
                               cfg["layer_norm_eps"]))


@pytest.fixture(scope="module")
def step(model):
    _cfg, net, params = model
    return jax.jit(lambda c, t, a: net.decode_step(params, c, t,
                                                   active=a))


def _chunked(net, params, cache, seqs, slots, upto, chunk):
    """Every sequence's first ``upto[i]`` tokens through
    `forward_chunk`, one one-row chunk of ``chunk`` a call as the
    engine runs them; the logits after each chunk, by (row, end)."""
    fn_ = jax.jit(lambda c, i, s, n, at: net.forward_chunk(
        params, c, i, s, n, slots=at))
    out = {}
    for r, (seq, slot, n) in enumerate(zip(seqs, slots, upto)):
        for off in range(0, n, chunk):
            m = min(chunk, n - off)
            row = np.zeros((1, chunk), np.int32)
            row[0, :m] = seq[off:off + m]
            cache, logits = fn_(cache, row, np.array([off], np.int32),
                                np.array([m], np.int32),
                                np.array([slot], np.int32))
            out[r, off + m] = np.asarray(logits[0])
    return cache, out


def _decode(step, cache, ids, want, at, row, n):
    """``n`` steps of the slots in ``at`` (slot: next position), each
    fed its sequence's token there; every slot's logits against the
    reference's."""
    slots = cache.seq_lens.shape[0]
    for j in range(n):
        active = np.array([s in at for s in range(slots)])
        tok = np.array([ids[row[s], at[s]] if s in at else 0
                        for s in range(slots)], np.int32)
        cache, logits = step(cache, tok, active)
        for s in at:
            assert np.abs(logits[s] - want[row[s], at[s]]).max() < \
                LOGIT_TOL, (j, s)
        at = {s: t + 1 for s, t in at.items()}
    return cache, at


# -- the model against the reference ----------------------------------

def test_call_is_the_reference_forward(ids, want, model):
    _cfg, net, params = model
    got = jax.jit(net.call)(params, jnp.asarray(ids))
    assert np.abs(want).max() > 2.0
    assert np.abs(np.asarray(got) - want).max() < LOGIT_TOL


def test_prefill_of_rows_of_different_lengths_then_decode(ids, want,
                                                          model, step):
    """One `prefill` of three right-padded prompts (30, 19 and 5
    tokens) into slots 3, 1 and 0, then 14 decode steps through the
    cache, past the window and around the ring; logits against the
    reference's one full pass."""
    _cfg, net, params = model
    plens = np.array([30, 19, 5])
    padded = np.zeros((3, 32), np.int32)
    for r in range(3):
        padded[r, :plens[r]] = ids[r, :plens[r]]
    cache = net.init_kv_cache(4, 64, page_size=4, max_chunk=CHUNK)
    cache, logits = jax.jit(net.prefill)(params, cache, padded, plens,
                                         np.array([3, 1, 0], np.int32))
    for r in range(3):
        assert np.abs(logits[r] - want[r, plens[r] - 1]).max() < \
            LOGIT_TOL
    cache, at = _decode(step, cache, ids, want, {3: 30, 1: 19, 0: 5},
                        {3: 0, 1: 1, 0: 2}, 14)
    assert list(np.asarray(cache.seq_lens)) == [at[0], at[1], 0, at[3]]


@pytest.mark.parametrize("chunk", [5, CHUNK])
def test_chunked_prefill_then_decode_matches_the_reference(
        ids, want, model, step, chunk):
    """Prompts of 37 and 26 tokens in chunks shorter (5) and longer
    (12) than the window, each chunk against the ring's last window
    and the full layer's pages, then decode steps."""
    _cfg, net, params = model
    cache = net.init_kv_cache(4, 64, page_size=4, max_chunk=CHUNK)
    cache, got = _chunked(net, params, cache, ids[:2], [2, 0], [37, 26],
                          chunk)
    for (r, end), v in got.items():
        assert np.abs(v - want[r, end - 1]).max() < LOGIT_TOL, (r, end)
    _decode(step, cache, ids, want, {2: 37, 0: 26}, {2: 0, 0: 1}, 6)


def _sequential(_parallel, params):
    """The same weights through the sequential block: a second norm,
    the first's gain, before the experts."""
    cfg = _share(0, 4, use_parallel_block=False)
    layers = [dict(p, norm2=p["norm1"]) for p in params["layers"]]
    return _net(cfg), dict(params, layers=layers)


def _rotate_half(net, params):
    for att in net.attentions:
        if att.rope is not None:
            att.rope = L.YarnRope(att.rope.dim, theta=att.rope.theta)
    return net, params


def _summed_shared(net, params):
    for ffn in net.feed_forward:
        ffn.shared_combine = "sum"
    return net, params


@pytest.mark.parametrize("departure", [_sequential, _rotate_half,
                                       _summed_shared],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_departure_in_the_program_leaves_the_reference(
        ids, want, model, departure):
    """A sequential block, a rotate-half rotary or a summed shared path
    in the program, the reference left alone, each moves the logits
    far past the tolerance."""
    cfg, _net_, params = model
    net, params = departure(_net(cfg), params)
    got = jax.jit(net.call)(params, jnp.asarray(ids))
    assert np.abs(np.asarray(got) - want).max() > 1e-2


# -- the parts --------------------------------------------------------

def test_interleaved_rotary_is_rotate_half_on_deinterleaved_values():
    """GPT-J's pairs (2i, 2i + 1) are rotate-half's (i, i + d/2) once
    the values are de-interleaved; on the same values the two
    conventions differ. The program's and the reference's agree."""
    x = jax.random.normal(jax.random.key(1), (5, 3, 16), F32)
    pos = jnp.arange(5)[:, None]
    gptj = L.YarnRope(16, theta=50000, interleaved=True)
    half = L.YarnRope(16, theta=50000)
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    got = np.asarray(gptj(x, pos))
    assert np.abs(got[..., perm] -
                  np.asarray(half(x[..., perm], pos))).max() < 1e-6
    assert np.abs(got - np.asarray(half(x, pos))).max() > 0.1
    assert np.abs(got - np.asarray(ref.rope_gptj(
        50000.0, x, pos))).max() < 1e-5


def test_two_shares_of_an_expert_layer_add_up_to_the_whole():
    """Each of 2 chips holds 4 of the 8 experts and computes the
    shared path alike, half of it under ``"average"``: the shares'
    outputs, that half counted once, are the uncut reference
    layer's."""
    x = jax.random.normal(jax.random.key(4), (24, 64), F32)
    whole = wc.layer(_share(0, 8), SEED, 1, F32)["ffn"]
    alike = 0.5 * gated_mlp(x, whole["shared_gate"], whole["shared_up"],
                            whole["shared_down"])
    total = alike
    for first in (0, 4):
        # every expert draws from its own key: a share's are the
        # whole layer's under the same ids
        p = {k: v[first:first + 4] if k.startswith("experts_") else v
             for k, v in whole.items()}
        moe = GroupLimitedMoE(
            64, 32, 8, 2, n_shared=2, experts_held=(first, 4),
            scoring="sigmoid", selection_bias=False, norm_topk=True,
            shared_combine="average")
        y, _counts = jax.jit(moe.__call__)(p, x)
        total = total + y - alike
    want = ref.moe(TOY, whole, x, (0, 8))
    assert np.abs(np.asarray(total - want)).max() < 1e-4
    assert np.abs(np.asarray(want)).max() > 0.1


def test_tied_head_tree_and_logit_scale(ids, model):
    """No ``lm_head`` leaf, no ``norm2`` and no selection bias; the
    logits are the final norm times the embedding's transpose, times
    ``logit_scale``."""
    cfg, net, params = model
    tree = jax.eval_shape(lambda: net.build(jax.random.key(0), (16,)))
    assert sorted(tree) == ["layers", "norm_f", "tok_embed"]
    assert all(sorted(p) == ["attn", "ffn", "norm1"]
               for p in tree["layers"])
    assert "router_bias" not in tree["layers"][0]["ffn"]
    h = jax.random.normal(jax.random.key(2), (3, 64), F32)
    plain = np.asarray(net._logits(params, h))
    normed = L.decoder.layer_norm(h, params["norm_f"], 1e-5)
    assert np.abs(plain - np.asarray(
        normed @ params["tok_embed"].T)).max() < 1e-5
    scaled = _net(dict(cfg, logit_scale=0.5))
    assert np.abs(np.asarray(scaled._logits(params, h)) -
                  0.5 * plain).max() < 1e-5


def test_built_tree_is_the_flops_count_and_the_weights_layout(model):
    cfg, net, _params = model
    tree = jax.eval_shape(lambda: net.build(jax.random.key(0), (16,)))
    assert fc.params(cfg)["total"] == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    made = jax.eval_shape(lambda: wc.weights(cfg, SEED, F32))
    assert jax.tree_util.tree_map(lambda a: a.shape, made) == \
        jax.tree_util.tree_map(lambda a: a.shape, tree)


def test_window_counters_sum_the_keys_read_and_causal(model):
    """A chunk call of rows at positions 0-11 and 10-14 and a step at
    lengths 20 and 3: each of the 3 window layers reads min(t + 1, 8)
    keys a query of the t + 1 it would read with no window."""
    _cfg, net, params = model
    names = net.step_counters
    read = names.index("zoo_tpu_window_keys_read_total")
    causal = names.index("zoo_tpu_window_keys_causal_total")
    assert names[causal + 1] == "zoo_tpu_window_pages_recycled_total"
    win = lambda ts: 3 * sum(min(t + 1, WINDOW) for t in ts)
    seen = lambda ts: 3 * sum(t + 1 for t in ts)
    cache = net.init_kv_cache(2, 64, page_size=4, max_chunk=CHUNK)
    counts = np.asarray(jax.jit(lambda c: net.forward_chunk(
        params, c, jnp.zeros((2, CHUNK), jnp.int32), jnp.array([0, 10]),
        jnp.array([12, 5]), slots=jnp.array([0, 1]), stats=True)[2])(
            cache))
    ts = list(range(12)) + list(range(10, 15))
    assert (counts[read], counts[causal]) == (win(ts), seen(ts))
    cache = cache._replace(seq_lens=jnp.array([20, 3], jnp.int32))
    counts = np.asarray(jax.jit(lambda c: net.decode_step(
        params, c, jnp.zeros((2,), jnp.int32), stats=True)[2])(cache))
    assert (counts[read], counts[causal]) == (win([20, 3]),
                                              seen([20, 3]))


def _gqa_parts(name, make):
    cfg = harness.load_named(harness.BENCH_DIR, "configs", name)
    net = make(dict(cfg, **{key: cfg["published"]["n_routed_experts"]
                            for key in ("n_routed_experts",
                                        "num_experts") if key in cfg}),
               n_layer=cfg["n_layer"], vocab=cfg["vocab_size"])
    return {a for a in net.attentions
            if isinstance(a, L.GroupedQueryAttention)}


@pytest.mark.parametrize("name, make, heads, block", [
    ("mimo-v2-flash-ep16", L.mimo_v2_flash_decoder, 64, 256),
    ("nemotron3-nano-ep8", L.nemotron_h_decoder, 32, 256),
    ("command-a-plus-ep8", L.command_a_decoder, 128, 64)])
def test_the_query_block_follows_the_head_count(name, make, heads,
                                                block):
    """MiMo's and Nemotron's grouped-query parts keep their query block
    of 256; at 128 heads it is 64, so that a block's f32 scores at the
    top rung (28672 keys) are 0.94 GB."""
    parts = _gqa_parts(name, make)
    assert parts and {(a.n_head, a.q_block) for a in parts} == \
        {(heads, block)}
    if heads == 128:
        assert heads * block * 28672 * 4 < 1e9


@pytest.mark.parametrize("key, value", [
    ("position_embedding_type", "rope"), ("use_qk_norm", True),
    ("layer_types", ["sliding_attention", "chunked_attention"] * 2),
    ("shared_expert_combination_strategy", "weighted"),
    ("expert_selection_fn", "softmax"),
    ("first_k_dense_replace", 1)])
def test_a_value_not_implemented_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match="not implemented"):
        L.command_a_decoder(dict(TOY, **{key: value}), n_layer=4)
