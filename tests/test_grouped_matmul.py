"""`ops/grouped_matmul.py` (`zoo_grouped_matmul`) at toy widths under
the Pallas interpreter: against `jax.lax.ragged_dot` and a float32
loop over the groups, at the row counts and held experts of the
benchmark's programs (96, 576 and 2048 x top-k rows; 16, 32 and 40
experts), with empty groups, one group holding every row, rows past
the last group and tiles the rows do not fill; `GroupLimitedMoE`
with both activations against its grouped products as
`jax.lax.ragged_dot` computed them; and the lowering guard: a toy
DeepSeek-V2 and a toy Nemotron-H program, lowered for the TPU (no
chip and no TPU library needed), hold one Mosaic body a distinct
product shape, not one a layer.

Tolerances, for bfloat16 operands with float32 accumulation: the
result is the float32 product rounded once to bfloat16 (8 significant
bits), half a unit in the last place (at most 2^-8 of its size) from
the float32 loop, and within one unit (2^-7) of `ragged_dot`, which
may round the other way; float32 operands agree with the loop to the
order of sums (1e-5 of the size).
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from analytics_zoo_tpu.ops import grouped_matmul as gm         # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras import layers as L   # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras.layers.moe import (  # noqa: E402
    GroupLimitedMoE)

BF, F32 = jnp.bfloat16, jnp.float32


def _rows(m, groups, held, seed):
    """``held`` of ``m`` rows spread over ``groups`` groups at random,
    a third of the groups left empty."""
    rng = np.random.default_rng(seed)
    p = rng.random(groups)
    p[rng.permutation(groups)[:groups // 3]] = 0.0
    return rng.multinomial(held, p / p.sum()).astype(np.int32)


def _operands(m, k, n, groups, dtype, seed):
    rng = np.random.default_rng(seed + 1)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((groups, k, n)), dtype)
    return x, w


def _loop(x, w, sizes):
    """Row ``r`` of group ``g``: ``x[r] @ w[g]`` in float32, one group
    at a time; rows past the last group 0."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    out = np.zeros((x.shape[0], w.shape[2]), np.float32)
    end = 0
    for g, size in enumerate(np.asarray(sizes)):
        out[end:end + size] = x[end:end + size] @ w[g]
        end += size
    return out


def _check(x, w, sizes):
    """The kernel against the float32 loop and `ragged_dot` on the
    rows the groups hold; returns its result."""
    got = gm.grouped_matmul(x, w, jnp.asarray(sizes))
    assert got.shape == (x.shape[0], w.shape[2]) and got.dtype == x.dtype
    held = int(np.sum(sizes))
    got = np.asarray(got, np.float32)[:held]
    want = _loop(x, w, sizes)[:held]
    scale = max(float(np.abs(want).max(initial=0.0)), 1.0)
    if x.dtype == F32:
        np.testing.assert_allclose(got, want, atol=1e-5 * scale)
        return got
    assert np.abs(got - want).max(initial=0.0) <= 2.0 ** -8 * scale
    ragged = np.asarray(jax.lax.ragged_dot(x, w, jnp.asarray(sizes)),
                        np.float32)[:held]
    assert np.abs(got - ragged).max(initial=0.0) <= 2.0 ** -7 * scale
    return got


# -- the benchmark's row counts and held experts -----------------------

@pytest.mark.parametrize("m, groups", [
    (96, 40), (96, 16), (128, 16), (576, 16), (576, 32),
    (2048 * 6, 40), (2048 * 8, 32), (2048 * 8, 16)])
@pytest.mark.parametrize("k, n", [(64, 48), (128, 40)])
def test_kernel_is_the_grouped_product(m, groups, k, n):
    """Rows sorted by group, a quarter of them past the last group (a
    layer's assignments to experts held elsewhere); ``k`` of 128 with
    ``n`` of 40 takes the kernel's transposed view of the weights."""
    sizes = _rows(m, groups, m - m // 4, seed=m + groups)
    _check(*_operands(m, k, n, groups, BF, seed=k), sizes)


@pytest.mark.parametrize("case", [
    "one_group_holds_every_row", "every_group_empty",
    "rows_fill_no_tile", "float32", "groups_of_one_row"])
def test_kernel_edges(case):
    m, k, n, groups = 576, 64, 48, 16
    dtype, sizes = BF, np.zeros(groups, np.int32)
    if case == "one_group_holds_every_row":
        sizes[5] = m
    elif case == "every_group_empty":
        pass
    elif case == "rows_fill_no_tile":
        # 40 rows: padded to 48, in tiles of 16 that groups share
        m = 40
        sizes[[1, 2, 9]] = [7, 13, 11]
    elif case == "float32":
        dtype, sizes = F32, _rows(m, groups, m, seed=7)
    else:
        sizes[:] = 1
    _check(*_operands(m, k, n, groups, dtype, seed=3), sizes)


@pytest.mark.parametrize("m, groups", [(96, 40), (576, 16),
                                       (2048 * 8, 32)])
def test_each_reached_group_is_streamed_once(m, groups):
    """The visits' table: every held row is in exactly one visit of
    its own group, the visits of a group follow each other (its
    weights are fetched once), an empty group has none, and the steps
    past the last visit repeat it with nothing to do."""
    tm, _ = gm.grouped_tiles(m, 64, 48, groups, 2)
    steps = m // tm + groups - 1
    sizes = _rows(m, groups, m - m // 4, seed=groups)
    group, tile, lo, hi = (np.asarray(a) for a in gm._visits(
        jnp.asarray(sizes), tm, steps))
    real = lo < hi
    n_real = int(real.sum())
    assert real[:n_real].all() and not real[n_real:].any()
    runs = [g for i, g in enumerate(group[:n_real])
            if i == 0 or g != group[i - 1]]
    assert runs == [g for g in range(groups) if sizes[g]]
    owner = np.repeat(np.arange(groups), sizes)
    seen = np.zeros(int(sizes.sum()), np.int32)
    for g, t, a, b in zip(group[:n_real], tile[:n_real], lo[:n_real],
                          hi[:n_real]):
        assert t * tm <= a < b <= (t + 1) * tm
        assert (owner[a:b] == g).all()
        seen[a:b] += 1
    assert (seen == 1).all()
    assert (group[n_real:] == group[n_real - 1]).all()
    assert (tile[n_real:] == tile[n_real - 1]).all()


def test_tiles_follow_the_shapes():
    """Rows: 16 in the decode steps (96 rows over 40 experts, 128 over
    16, 64 over 32), 32 in the Nemotron step (576 over 16), 128 in a
    2048-token block; columns: the whole width up to a 16 MiB block
    of weights, every configuration's expert among them."""
    assert gm.grouped_tiles(96, 5120, 1536, 40, 2) == (16, 1536)
    assert gm.grouped_tiles(128, 4096, 2048, 16, 2) == (16, 2048)
    assert gm.grouped_tiles(64, 5120, 1536, 32, 2) == (16, 1536)
    assert gm.grouped_tiles(576, 2688, 1856, 16, 2) == (32, 1856)
    assert gm.grouped_tiles(16384, 1536, 5120, 32, 2) == (128, 5120)
    assert gm.grouped_tiles(12288, 2688, 1856, 16, 2) == (128, 1856)
    # wider than a block: the widest 128-multiple that fits
    assert gm.grouped_tiles(128, 8192, 4096, 16, 2) == (16, 1024)


# -- the expert layer against its ragged_dot form ----------------------

def _ragged_routed(moe, p, x, experts, weights, valid):
    """`GroupLimitedMoE.routed`'s result with its grouped products as
    `jax.lax.ragged_dot`, the layer's form before the kernel."""
    n, k = experts.shape
    local = experts - moe.first
    held = jnp.logical_and(local >= 0, local < moe.n_held)
    held = jnp.logical_and(held, valid[:, None]).reshape(-1)
    key = jnp.where(held, local.reshape(-1), moe.n_held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((moe.n_held + 1,), jnp.int32).at[key].add(
        1)[:moe.n_held]
    rows = jnp.take(x, order // k, axis=0)
    dot = lambda a, name: jax.lax.ragged_dot(a, p[name], sizes)
    if moe.activation == "relu2":
        act = jnp.square(jax.nn.relu(dot(rows, "experts_up")))
    else:
        act = jax.nn.silu(dot(rows, "experts_gate")) * \
            dot(rows, "experts_up")
    out = dot(act, "experts_down")
    out = jnp.where(held[:, None],
                    jnp.take(out, jnp.argsort(order), axis=0), 0)
    y = (out.astype(F32) * weights.reshape(-1, 1)).reshape(
        n, k, -1).sum(axis=1)
    return y.astype(x.dtype), sizes


@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
@pytest.mark.parametrize("tokens, block", [(32, 2048), (96, 32)])
def test_expert_layer_is_its_ragged_dot_form(activation, tokens, block):
    """Experts 4-11 of 16 held, top-3, a fifth of the tokens not
    valid, bfloat16; 96 tokens in blocks of 32 take the block-by-block
    path, whose reached count sums the blocks'. The two sides round
    the first products' rows alike but for a flipped last place, so
    the outputs (of size about 1) agree to 2^-6 of their largest."""
    moe = GroupLimitedMoE(64, 32, 16, 3, experts_held=(4, 8),
                          scoring="sigmoid", norm_topk=True,
                          activation=activation, token_block=block)
    p = jax.tree_util.tree_map(
        lambda a: a.astype(BF) if a.ndim > 1 else a,
        moe.build(jax.random.key(1), 0.2))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((tokens, 64)), BF)
    valid = jnp.asarray(rng.random(tokens) > 0.2)
    experts, weights = moe.route(p, x)
    y, counts = moe.routed(p, x, experts, weights, valid)
    want, sizes = _ragged_routed(moe, p, x, experts, weights, valid)
    scale = float(jnp.abs(want.astype(F32)).max())
    assert scale > 0.1
    assert float(jnp.abs(y.astype(F32) - want.astype(F32)).max()) <= \
        2.0 ** -6 * scale
    total, held, busiest, reached = (int(c) for c in counts)
    assert total == 3 * int(valid.sum())
    assert held == int(sizes.sum()) and busiest == int(sizes.max())
    assert reached == int((sizes > 0).sum())
    full, fcounts = moe(p, x, valid)
    assert int(fcounts[3]) >= reached
    if tokens <= block:
        assert (np.asarray(fcounts) == np.asarray(counts)).all()
        np.testing.assert_array_equal(np.asarray(full), np.asarray(y))
    else:
        parts = [moe.routed(p, x[i:i + block], experts[i:i + block],
                            weights[i:i + block], valid[i:i + block])[1]
                 for i in range(0, tokens, block)]
        assert int(fcounts[3]) == sum(int(c[3]) for c in parts)


# -- the lowering guard -------------------------------------------------

_DEEPSEEK = dict(
    name="toy", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=4, n_layer=4,
    first_k_dense_replace=1, moe_layer_freq=1, n_routed_experts=16,
    n_shared_experts=2, num_experts_per_tok=3, n_group=4, topk_group=2,
    routed_scaling_factor=2.0, num_attention_heads=8, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=None, vocab_size=100, max_position_embeddings=256,
    initializer_range=0.2)
_NEMOTRON = dict(
    name="toy", hidden_size=64, mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    mamba_hidden_act="silu", mlp_hidden_act="relu2",
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=3,
    routed_scaling_factor=2.5, norm_topk_prob=True, n_group=1,
    topk_group=1, hybrid_override_pattern="MEM*EMEM",
    num_hidden_layers=8, n_layer=8, layer_norm_epsilon=1e-5,
    vocab_size=100, max_position_embeddings=256, initializer_range=0.2)


@pytest.mark.parametrize("model, program", [
    ("deepseek_v2", "step"), ("deepseek_v2", "prefill"),
    ("nemotron_h", "step"), ("nemotron_h", "prefill")])
def test_a_program_lowers_one_body_a_product_shape(monkeypatch, model,
                                                   program):
    """Lowered for the TPU as the chip compiles it (the kernel, not
    its interpreter): three expert layers (DeepSeek-V2, SwiGLU; Nemotron-H,
    relu^2) of 8 held experts call the grouped product
    two or three times each, and the module holds one Mosaic body for
    each distinct shape of product among those calls (SwiGLU's gate
    and up share theirs), not one a call."""
    monkeypatch.setattr(gm, "on_tpu", lambda: True)
    cfg = _DEEPSEEK if model == "deepseek_v2" else _NEMOTRON
    build = getattr(L, f"{model}_decoder")
    net = build(cfg, n_layer=cfg["n_layer"], experts_held=(4, 8))
    layers = sum(isinstance(f, GroupLimitedMoE) for f in net.feed_forward)
    assert layers == 3
    params = jax.eval_shape(lambda: net.build(jax.random.key(0), (16,)))
    slots = 4
    cache = jax.eval_shape(lambda: net.init_kv_cache(
        slots, 64, page_size=4))
    i32 = lambda *d: jax.ShapeDtypeStruct(d, jnp.int32)
    if program == "step":
        def fn(cache, params, tok, active):
            return net.decode_step(params, cache, tok, active=active,
                                   stats=True)
        args = (i32(slots), jax.ShapeDtypeStruct((slots,), jnp.bool_))
    else:
        def fn(cache, params, ids, plens, slot):
            return net.prefill(params, cache, ids, plens, slot,
                               stats=True)
        args = (i32(1, 32), i32(1), i32(1))
    text = jax.jit(fn).trace(cache, params, *args).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = re.findall(r"call @_grouped_matmul\w*\(.*?\) : \((.*?)\)",
                       text)
    products = 3 if model == "deepseek_v2" else 2
    assert len(calls) == layers * products, len(calls)
    bodies = re.findall(r'tpu_custom_call[^\n]*kernel_name = '
                        r'"zoo_grouped_matmul"', text)
    assert len(bodies) == len(set(calls)) == 2, (len(bodies),
                                                 set(calls))
