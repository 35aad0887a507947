"""`PatternDecoder` at a toy size against the benchmark's plain
float32 reference of DeepSeek-V2 (`benchmark/reference/deepseek_v2.py`,
which imports nothing of the program): 3 layers (one dense, two of
experts), hidden 64, 8 heads, 16 experts in 4 groups of which 2 are
kept, top-3, 2 shared experts, a latent row of 16 + 8 values.

Weights are float32 and the CPU multiplies float32 exactly (the
reference asks for ``highest`` besides), so what separates the two
sides is the order of float32 sums
(absorbed against expanded attention, a grouped product over sorted
assignments against a loop over experts): logits of magnitude 2-6
agree to 1e-4, forty times the largest difference seen (2.5e-6), and
the float8 control differs by more than 0.1.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from analytics_zoo_tpu.ops import kv_cache as kvc              # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras import layers as L   # noqa: E402
from analytics_zoo_tpu.pipeline.inference import (             # noqa: E402
    InferenceModel)
from analytics_zoo_tpu.pipeline.inference.batching import (    # noqa: E402
    ContinuousBatcher)
from analytics_zoo_tpu.pipeline.inference.generation import (  # noqa: E402
    GenerationEngine)
from benchmark import weights_deepseek as wd                   # noqa: E402
from benchmark.reference import deepseek_v2 as ref             # noqa: E402

F32 = jnp.float32
# order of float32 sums only (see the module docstring)
LOGIT_TOL = 1e-4
YARN = dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707,
            mscale_all_dim=0.707, type="yarn")
TOY = dict(
    name="toy", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, n_layer=3,
    first_k_dense_replace=1, moe_layer_freq=1, n_routed_experts=16,
    n_shared_experts=2, num_experts_per_tok=3, n_group=4, topk_group=2,
    routed_scaling_factor=2.0, num_attention_heads=8, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(YARN, original_max_position_embeddings=64),
    vocab_size=100, max_position_embeddings=256,
    # wide enough that the routing has margins and logits reach 2-6
    initializer_range=0.2)
SEED = 2 ** 31 + 5


def _share(first, count):
    """TOY as one chip's share holds it: experts [first, first +
    count) of the 16."""
    return dict(TOY, n_routed_experts=count,
                published={"n_routed_experts": 16},
                held={"experts": [first, first + count]})


def _net(cfg, **kw):
    return L.deepseek_v2_decoder(
        dict(cfg, n_routed_experts=wd.experts_total(cfg)),
        n_layer=cfg["n_layer"], experts_held=wd.experts_held(cfg),
        **kw)


def _reference_logits(cfg, ids, quant=False):
    emb = wd.embeddings(cfg, SEED, F32)
    hid = ref.hidden(cfg, emb, lambda i: wd.layer(cfg, SEED, i, F32),
                     ids, wd.experts_held(cfg), quant=quant)
    return np.asarray(ref.head(hid, emb["norm_f"], emb["lm_head"],
                               cfg["rms_norm_eps"], quant=quant))


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(3).randint(0, 100, (3, 24))


# -- the model against the reference ----------------------------------

@pytest.mark.parametrize("held", [(0, 16), (0, 8), (4, 4)])
def test_prefill_then_cached_decode_matches_the_reference(ids, held):
    """Prompts of unequal length through `prefill`, then every
    further token through `decode_step` and the paged latent cache,
    against the reference's one full pass, on logits; a slot that
    stays empty and one that stops early are not disturbed and
    disturb nothing."""
    cfg = _share(*held)
    net, params = _net(cfg), wd.weights(cfg, SEED, F32)
    want = _reference_logits(cfg, ids)
    assert np.abs(want).max() > 2.0
    plens = np.array([5, 9, 0, 7])
    padded = np.zeros((4, 16), np.int32)
    rows = [0, 1, None, 2]                 # slot -> row of ids
    for s, r in enumerate(rows):
        if r is not None:
            padded[s, :plens[s]] = ids[r, :plens[s]]
    cache = net.init_kv_cache(4, 32, page_size=4)
    cache, logits = jax.jit(net.prefill)(params, cache, padded, plens)
    worst = 0.0
    for s, r in enumerate(rows):
        if r is not None:
            worst = max(worst, np.abs(
                logits[s] - want[r, plens[s] - 1]).max())
    step = jax.jit(lambda c, t, a: net.decode_step(params, c, t,
                                                   active=a))
    at = plens.copy()
    for j in range(14):
        active = np.array([r is not None and at[s] < 24 and
                           not (s == 3 and j >= 6)
                           for s, r in enumerate(rows)])
        tok = np.array([ids[r, at[s]] if active[s] else 0
                        for s, r in enumerate(rows)], np.int32)
        cache, logits = step(cache, tok, active)
        for s, r in enumerate(rows):
            if active[s]:
                worst = max(worst, np.abs(
                    logits[s] - want[r, at[s]]).max())
        at += active
    assert list(np.asarray(cache.seq_lens)) == list(at)
    assert at[2] == 0 and at[3] == 13
    assert worst < LOGIT_TOL, worst


def test_float8_control_fails_the_tolerance(ids):
    cfg = _share(0, 8)
    gap = np.abs(_reference_logits(cfg, ids, quant=True) -
                 _reference_logits(cfg, ids)).max()
    assert gap > 1000 * LOGIT_TOL, gap


def test_call_is_the_reference_forward(ids):
    cfg = _share(0, 8)
    got = _net(cfg).call(wd.weights(cfg, SEED, F32), jnp.asarray(ids))
    assert np.abs(np.asarray(got) - _reference_logits(cfg, ids)
                  ).max() < LOGIT_TOL


def test_absorbed_attention_is_expanded_attention():
    """One layer's attention alone: the last position of the
    expanded form over a prompt, against the absorbed form for that
    token over the rows the prompt left in a (dense) latent view."""
    cfg = _share(0, 8)
    whole = _net(cfg).attention
    assert whole.head_block == whole.n_head == 8
    # the expanded form a block of heads at a time is the same sum
    att = L.LatentAttention(64, 8, 24, 16, 16, 8, 16, whole.rope,
                            head_block=2)
    p = wd.layer(cfg, SEED, 1, F32)["attn"]
    x = jax.random.normal(jax.random.key(1), (2, 12, 64), F32)
    out, rows = att.prefill(p, x, impl="xla")
    assert rows.shape == (2, 12, 24)
    assert np.abs(np.asarray(
        out - whole.prefill(p, x, impl="xla")[0])).max() < 1e-5
    pos = jnp.array([11, 11])

    def view(row):
        ctx = jnp.pad(rows.at[:, 11].set(row), [(0, 0), (0, 4), (0, 8)])
        return ctx, row

    got, row = att.decode(p, x[:, 11], pos, view, pos + 1)
    # the same float32 sums in another order
    assert np.abs(np.asarray(row - rows[:, 11])).max() < 1e-6
    assert np.abs(np.asarray(got - out[:, 11])).max() < 1e-5


def test_routing_is_the_references():
    """Groups kept, experts chosen and their weights, token by
    token; the weights are the softmax scores times the scaling
    factor, not renormalised."""
    cfg = _share(0, 16)
    moe = _net(cfg).feed_forward[1]
    p = wd.layer(cfg, SEED, 1, F32)["ffn"]
    x = jax.random.normal(jax.random.key(2), (64, 64), F32)
    experts, weights = moe.route(p, x)
    kept, want_e, want_w = ref.route(cfg, p["router"], x)
    assert (np.sort(np.asarray(experts)) ==
            np.sort(np.asarray(want_e))).all()
    assert np.allclose(np.sort(np.asarray(weights)),
                       np.sort(np.asarray(want_w)), rtol=1e-6)
    groups = np.asarray(experts) // 4
    for row, keep in zip(groups, np.asarray(kept)):
        assert set(row) <= set(keep)
    assert len({tuple(sorted(k)) for k in np.asarray(kept)}) > 1
    scores = jax.nn.softmax(x @ p["router"], axis=-1)
    assert np.allclose(np.asarray(weights), 2.0 * np.take_along_axis(
        np.asarray(scores), np.asarray(experts), 1), rtol=1e-5)
    assert not np.allclose(np.asarray(weights).sum(1), 2.0)


def test_yarn_frequencies_and_softmax_scale_as_published():
    """DeepSeek-V2's own settings, worked by hand: the correction
    dimensions of 32 and 1 rotations over 4096 positions are
    64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 -> 10 and
    64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23, so pairs
    0-10 keep 10000^(-i/32), pairs 23-31 are that over 40 and the
    ramp between is (i - 10) / 13; mscale = 0.1 * 0.707 * ln 40 + 1
    = 1.260804, s = 192^-0.5 * mscale^2 = 0.114721; cos and sin are
    scaled by mscale / mscale = 1."""
    cfg = dict(qk_rope_head_dim=64, qk_nope_head_dim=128,
               rope_theta=10000,
               rope_scaling=dict(
                   YARN, original_max_position_embeddings=4096))
    rope = L.YarnRope(64, factor=40, original_max_position=4096,
                      mscale=0.707, mscale_all_dim=0.707)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    want = plain.copy()
    for i in range(11, 23):
        ramp = (i - 10) / 13.0
        want[i] = plain[i] / 40 * ramp + plain[i] * (1 - ramp)
    want[23:] = plain[23:] / 40
    for got in (rope.inv_freq(), ref.yarn_inv_freq(cfg)):
        assert np.allclose(got, want, rtol=1e-12)
    assert abs(want[16] - 10000.0 ** -0.5 * (6 / 13 / 40 + 7 / 13)) \
        < 1e-15
    att = L.LatentAttention(5120, 128, 1536, 512, 128, 64, 128, rope)
    for got in (att.scale, ref.softmax_scale(cfg)):
        assert abs(got - 0.114721) < 5e-7, got
    assert rope.cos_sin_scale == 1.0
    assert att.row_width == 576
    # plain RoPE where the config has no scaling
    assert np.allclose(L.YarnRope(64).inv_freq(), plain)
    assert L.YarnRope(64).attention_mscale == 1.0
    # rotate-half: pair i is (x[i], x[i + 32]), turned by pos * f_i
    x = jnp.zeros((64,), F32).at[3].set(1.0)
    y = np.asarray(rope(x, jnp.asarray(5)))
    assert abs(y[3] - np.cos(5 * want[3])) < 1e-6
    assert abs(y[35] - np.sin(5 * want[3])) < 1e-6


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One expert layer, four chips with four experts each: the
    routed parts of the four shares plus the shared experts counted
    once are the uncut reference's layer."""
    whole = _share(0, 16)
    p_all = wd.layer(whole, SEED, 1, F32)
    x = jax.random.normal(jax.random.key(4), (48, 64), F32)
    want = ref._moe(whole, {k: v.astype(F32) for k, v in
                            p_all["ffn"].items()}, x, (0, 16), False)
    shared = ref._swiglu(x, p_all["ffn"]["shared_gate"],
                         p_all["ffn"]["shared_up"],
                         p_all["ffn"]["shared_down"], False)
    total, held_sum = shared, 0
    for chip in range(4):
        cfg = _share(4 * chip, 4)
        p = wd.layer(cfg, SEED, 1, F32)["ffn"]
        assert (p["experts_up"] ==
                p_all["ffn"]["experts_up"][4 * chip:4 * chip + 4]).all()
        moe = _net(cfg).feed_forward[1]
        y, counts = moe(p, x)
        total = total + (y - shared)
        assert int(counts[0]) == 48 * 3
        held_sum += int(counts[1])
        assert 0 < int(counts[2]) <= 48
    assert held_sum == 48 * 3          # every assignment lands once
    assert np.abs(np.asarray(total - want)).max() < 1e-5
    assert np.abs(np.asarray(want - shared)).max() > 0.1


def test_tokens_not_valid_reach_no_expert():
    cfg = _share(0, 8)
    moe = _net(cfg).feed_forward[1]
    p = wd.layer(cfg, SEED, 1, F32)["ffn"]
    x = jax.random.normal(jax.random.key(5), (8, 64), F32)
    valid = jnp.arange(8) < 3
    y, counts = moe(p, x, valid)
    full, _ = moe(p, x)
    assert int(counts[0]) == 9 and int(counts[1]) <= 9
    assert np.allclose(np.asarray(y[:3]), np.asarray(full[:3]),
                       atol=1e-6)
    # block by block is the same sum
    blocked = L.GroupLimitedMoE(64, 32, 16, 3, n_group=4, topk_group=2,
                                n_shared=2, routed_scaling=2.0,
                                experts_held=(0, 8), token_block=4)
    yb, cb = blocked(p, x)
    assert np.allclose(np.asarray(yb), np.asarray(full), atol=1e-6)
    assert int(cb[0]) == 24


# -- the latent cache -------------------------------------------------

def test_latent_pool_is_one_padded_pool():
    net = _net(_share(0, 8))
    cache = net.init_kv_cache(4, 30, page_size=4)
    assert isinstance(cache, kvc.RowPagedCache)
    assert cache.pages.shape == (3, 4 * 8, 4, kvc.ROW_ALIGN)
    assert (cache.max_context, cache.max_slots, cache.page_size,
            cache.num_pages) == (32, 4, 4, 32)
    wide = kvc.init_row_cache(5, 2, 64, 576, dtype=jnp.bfloat16)
    assert wide.pages.shape[-1] == 640
    assert wide.pool_dtype == jnp.bfloat16


def test_int8_latent_cache_is_refused_by_name():
    with pytest.raises(ValueError, match="row cache"):
        _net(_share(0, 8)).init_kv_cache(2, 16, dtype=jnp.int8)
    with pytest.raises(ValueError, match="row cache"):
        GenerationEngine(_net(_share(0, 8)), {}, max_slots=2,
                         max_context=16, cache_dtype="int8")


# -- the engine and the batcher ---------------------------------------

def _engine(cfg, **kw):
    kw = {"max_slots": 3, "max_context": 32, "page_size": 4, **kw}
    return GenerationEngine(_net(cfg), wd.weights(cfg, SEED, F32),
                            **kw)


class _NoChunk:
    """A net that truly lacks ``forward_chunk`` (every decoder of
    the package has it now)."""
    seq_len, vocab = 32, 100


def test_engine_refuses_what_the_decoder_lacks():
    cfg = _share(0, 8)
    with pytest.raises(ValueError, match="forward_chunk"):
        GenerationEngine(_NoChunk(), {}, prefill_chunk=8)
    with pytest.raises(ValueError, match="forward_chunk"):
        GenerationEngine(_NoChunk(), {}, spec_k=2,
                         drafter=_net(cfg), drafter_params={})
    with pytest.raises(TypeError, match="row cache"):
        _engine(cfg, role="prefill")
    eng = _engine(cfg)
    with pytest.raises(TypeError, match="row cache"):
        eng.export_handoff(0)
    with pytest.raises(TypeError, match="row cache"):
        eng.admit_from_handoff({"version": kvc.HANDOFF_VERSION}, 4)
    with pytest.raises(ValueError, match="positions"):
        _engine(cfg, max_context=512)


def test_batcher_serves_the_decoder_with_slots_joining_and_leaving():
    """Nine greedy requests of unequal lengths over three slots, run
    alone: every answer is what the whole-loop `generate` gives for
    that prompt by itself, nothing compiles after warm-up, and the
    expert counts of the steps and of the whole-prompt prefills
    reach the counters with the tokens."""
    from analytics_zoo_tpu.common import observability as obs
    from jax import monitoring
    cfg = _share(0, 8)
    im = InferenceModel()
    im.load_generator(_net(cfg), wd.weights(cfg, SEED, F32),
                      max_slots=3, max_context=32, page_size=4)
    eng = im.generator
    rs = np.random.RandomState(11)
    sizes = [(1, 3), (3, 5), (2, 4), (8, 6), (15, 2), (9, 7), (5, 9),
             (12, 1), (7, 7)]
    prompts = [rs.randint(0, 100, size=n).tolist() for n, _ in sizes]
    compiles, armed = [], [False]

    def listener(name, dur, **kw):
        if armed[0] and name.endswith("backend_compile_duration"):
            compiles.append(name)

    def counter(name):
        fam = obs.snapshot().get(name)
        return sum(v["value"] for v in fam["values"]) if fam else 0

    monitoring.register_event_duration_secs_listener(listener)
    cb = ContinuousBatcher(eng, queue_depth=32)
    names = eng.net.step_counters
    assert len(names) == 4 and names[0].startswith("zoo_tpu_moe_")
    before = [counter(n) for n in names]
    steps0 = counter("zoo_tpu_serving_gen_tokens_total")
    try:
        cb.start()
        armed[0] = True
        futs = []
        for p, (_, m) in zip(prompts, sizes):
            futs.append(cb.submit(p, max_new_tokens=m))
            time.sleep(0.002)
        outs = [f.result(timeout=120) for f in futs]
        armed[0] = False
    finally:
        armed[0] = False
        cb.stop()
    assert compiles == []
    for p, (_, m), out in zip(prompts, sizes, outs):
        alone = eng.generate([p], max_new_tokens=m)[0]
        assert list(out) == list(alone), (len(p), m)
    total, held, busiest, reached = (counter(n) - b
                                     for n, b in zip(names, before))
    decoded = counter("zoo_tpu_serving_gen_tokens_total") - steps0
    assert decoded == sum(m - 1 for _, m in sizes)
    # top-3, two expert layers; a prompt's tokens count as a chunk's
    assert total == (decoded + sum(n for n, _ in sizes)) * 3 * 2
    assert 0 < busiest <= held < total
    # an expert a call reached took at least one of its rows
    assert 0 < reached <= held
