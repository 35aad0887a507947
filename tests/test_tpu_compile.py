"""Chip compiles kept among the tests: every Pallas kernel of
chip_smoke.py's phase 4 at its real T=4096 shape, the ResNet-50
b128 train step and the generation programs, compiled by the TPU
compiler for a *described* v5e (no chip attached, nothing runs).
Interpret mode cannot see what this sees: slices not aligned to the
tiling, a kernel that wants more VMEM than it may use.

The one file that does this (a second file could land on another
xdist worker, whose fixture would then skip every test): the
topology is described inside a module-scoped fixture — never at
import, in a ``skipif`` or in ``parametrize`` — because only one
process at a time may hold the TPU library.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from analytics_zoo_tpu.ops import flash_attention as fa
from analytics_zoo_tpu.ops import grouped_matmul as gm
from analytics_zoo_tpu.ops import kv_cache as kvc
from analytics_zoo_tpu.perf import autotune

BF, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    """A described v5e host of four chips; the persistent compilation
    cache is off around these compiles (an entry written for a
    described chip cannot be read back without one, and warns)."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _v5e_tile_tables(monkeypatch):
    """Tile decisions as the chip makes them: the code asks
    `jax.devices()`, which is the CPU here, so point the autotuner at
    the committed v5e table, and compile the routed experts' grouped
    product as its kernel rather than its interpreter."""
    monkeypatch.setattr(autotune, "_device", "v5e")
    monkeypatch.setattr(gm, "on_tpu", lambda: True)
    autotune.reset_cache()
    yield
    autotune.reset_cache()


# ---------------------------------------------------------------------
# the kernels: name -> (function, [(shape, dtype), ...])
# ---------------------------------------------------------------------

_ATT = (4, 4096, 16, 64)          # B, T, H, D
# the routed experts' grouped product: the rows of its call sites as
# lowered and of its kernel as compiled
_GROUPED_LOWERED = r"call @_grouped_matmul\w*\(.*?\) : \(tensor<(\d+)x"
_GROUPED_COMPILED = r"%zoo_grouped_matmul[\w.]* = \w+\[(\d+),"
_DEC = dict(s=8, t=4096, h=12, d=64)


def _flash(q, k, v):
    return fa.flash_attention(q, k, v, causal=True, interpret=False)


def _flash_grads(q, k, v):
    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v).astype(F32) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _flash_masked(q, k, v, mask):
    return fa.flash_attention(q, k, v, key_mask=mask, interpret=False)


def _flash_partial(q, k, v):
    return fa.flash_block_partial(q, k, v, jnp.int32(0), causal=True,
                                  scale=0.125, interpret=False)


def _decode(q, k, v, mask, ks=None, vs=None):
    return fa.flash_decode_attention(
        q, k, v, mask, scale=0.125, interpret=False, k_scales=ks,
        v_scales=vs)


def _paged_decode(q, k_pages, v_pages, table, lens, layer):
    return fa.paged_decode_partial(
        q, k_pages, v_pages, table, lens, layer[0], heads=25,
        head_dim=64, scale=0.125, interpret=False)


def _flash_chunk(q, k, v, mask):
    bq, bk = fa.chunk_blocks(q.shape[1], k.shape[1])
    return fa.masked_chunk_attention(q, k, v, mask, 0.07, block_q=bq,
                                     block_k=bk, interpret=False)


def _grouped_shapes(rows, k, n, experts):
    return [((rows, k), BF), ((experts, k, n), BF),
            ((experts,), jnp.int32)]


def _chunk_shapes(keys, width):
    """dots3-note's chunk attention: 2048 queries of a head block of
    16 against ``keys`` keys ``width`` wide and values of 128."""
    return [((1, 2048, 16, width), BF), ((1, keys, 16, width), BF),
            ((1, keys, 16, 128), BF), ((1, 2048, keys), jnp.bool_)]


# GPT-2-XL's cell: 8 slots of 64 pages of 16 rows of 25 x 64 -> 1664
_PAGED = [((8, 1664), BF)] + [((4, 512, 16, 1664), BF)] * 2 + \
    [((8, 64), jnp.int32), ((8,), jnp.int32), ((1,), jnp.int32)]

_QKV = [(_ATT, BF)] * 3
_HALF = [((4, 2048, 16, 64), BF)] * 3
_DQKV = [((_DEC["s"], _DEC["h"], _DEC["d"]), BF)] + \
    [((_DEC["s"], _DEC["t"], _DEC["h"], _DEC["d"]), BF)] * 2
_DMASK = [((_DEC["s"], _DEC["t"]), jnp.bool_)]
_DSCALES = [((_DEC["s"], _DEC["t"], _DEC["h"]), F32)] * 2

KERNELS = {
    "flash_fwd": (_flash, _QKV),
    "flash_fwd_bwd": (_flash_grads, _QKV),
    "flash_masked_fwd": (_flash_masked, _QKV + [((4, 4096), F32)]),
    "flash_block_partial": (_flash_partial, _HALF),
    "flash_decode_bf16": (_decode, _DQKV + _DMASK),
    "flash_decode_int8": (
        _decode,
        [_DQKV[0]] + [(_DQKV[1][0], jnp.int8)] * 2 + _DMASK + _DSCALES),
    "paged_decode": (_paged_decode, _PAGED),
    # a full layer behind the longest bucket; a sliding layer's ring of
    # 2576 and the chunk, padded to whole key blocks inside
    "flash_chunk_full": (_flash_chunk, _chunk_shapes(34816, 192)),
    "flash_chunk_window": (_flash_chunk, _chunk_shapes(4624, 256)),
    # the routed experts: Nemotron-H's step (96 slots x 6, its 16 held
    # relu^2 experts, up and down) and a 2048-token block of dots3-note
    # (x 8, 32 held SwiGLU experts)
    "grouped_matmul_nemotron_up": (
        gm.grouped_matmul, _grouped_shapes(576, 2688, 1856, 16)),
    "grouped_matmul_nemotron_down": (
        gm.grouped_matmul, _grouped_shapes(576, 1856, 2688, 16)),
    "grouped_matmul_chunk_block": (
        gm.grouped_matmul, _grouped_shapes(16384, 5120, 1536, 32)),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: the Pallas kernel is not in the compiled program"
    if name.startswith("grouped_matmul"):
        # the experts are read where they lie: no copy of them first
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 8 << 20, mem.temp_size_in_bytes


def test_int8_decode_operands_match_the_cache_codec():
    # the int8 case above feeds (int8 rows, f32 per-(token, head)
    # scales): exactly what the cache's own quantizer emits
    k = jnp.ones((2, 128, 2, 64), BF)
    q, scale = kvc.quantize_rows(k)
    assert q.dtype == jnp.int8 and scale.dtype == F32
    assert q.shape == k.shape and scale.shape == k.shape[:-1]


# ---------------------------------------------------------------------
# the ResNet-50 train step as both train cells run it: Estimator's own
# jitted step, batch 128 a chip, mixed_bfloat16, SGD with momentum
# ---------------------------------------------------------------------

_V5E_BYTES = 15.75e9          # what the v5e's compiler allows a program


def _resnet_step(topo, chips):
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.models.image.imageclassification import \
        resnet50
    from analytics_zoo_tpu.ops.optimizers import SGD
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    model = resnet50(input_shape=(224, 224, 3), classes=1000)
    est = Estimator(model, optimizer=SGD(lr=0.002, momentum=0.9),
                    loss="softmax_cross_entropy",
                    ctx=init_nncontext(log_level="WARNING"),
                    dtype_policy="mixed_bfloat16")
    est.params = jax.eval_shape(
        lambda: model.init_params(jax.random.key(0)))
    tx = est._tx()
    if chips == 1:
        whole = rows = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
        whole = NamedSharding(mesh, PartitionSpec())
        rows = NamedSharding(mesh, PartitionSpec("data"))

    def on(tree, sharding):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), tree)
    state = on((est.params, jax.eval_shape(tx.init, est.params)),
               whole)
    batch = 128 * chips
    return state, est._build_train_step(tx).lower(
        *state, jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole),
        jax.ShapeDtypeStruct((batch, 224, 224, 3), F32, sharding=rows),
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=rows)
    ).compile()


@pytest.mark.parametrize("chips", [1, 4])
def test_resnet50_train_step_is_all_xla_and_fits(topo, chips):
    """One ResNet path: no Pallas kernel in the step; arguments,
    results and temporaries inside 15.75 GB a chip; parameters and
    optimiser state donated, so the new ones are written over the
    old; over a data: 4 mesh the gradients meet in an all-reduce."""
    import re
    state, compiled = _resnet_step(topo, chips)
    hlo = compiled.as_text()
    assert "tpu_custom_call" not in hlo
    assert len(re.findall(r" convolution\(", hlo)) == 161
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes - \
        mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert held < _V5E_BYTES, held
    donated = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(state))
    assert mem.alias_size_in_bytes >= donated, (
        mem.alias_size_in_bytes, donated)
    assert ("all-reduce" in hlo) == (chips == 4)


# ---------------------------------------------------------------------
# the generation engine's programs at GPT-2-XL's widths: what the v5e
# compiler does with the KV page pools (nothing runs, nothing is timed)
# ---------------------------------------------------------------------

_XL = dict(hidden_size=1600, n_head=25, seq_len=1024, vocab=50257,
           intermediate_size=6400)
_XL_BLOCKS, _XL_SLOTS, _XL_PAGE = 4, 8, 16    # the scan body compiles once
_XL_DEPTH = 48                # the prefill at the published depth: its
#                               temporaries grow with the layers


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The rule that chooses the paged decode kernel asks
    `jax.devices()`, the CPU here: answer as the chip would, and
    compile the kernel rather than interpret it."""
    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    monkeypatch.setattr(fa, "on_tpu", lambda: True)


def _xl_program(one_chip, program, cache_dtype=BF):
    """("step" | "prefill") -> (cache shapes, compiled): the decode
    step over every slot, or the engine's longest prefill program,
    ONE prompt row of 1024 tokens addressed by slot."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.transformer \
        import TransformerLayer
    # "auto" asks jax.devices(), the CPU here: name the kernel the
    # chip's "auto" takes from 1024 keys up
    net = TransformerLayer(
        n_block=_XL_BLOCKS if program == "step" else _XL_DEPTH,
        hidden_p_drop=0.0, attn_p_drop=0.0, embed_p_drop=0.0,
        attention_impl=None if program == "step" else "flash", **_XL)

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, dtype if dtype and a.dtype == F32 else a.dtype,
                sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: net.build(jax.random.key(0), (_XL["seq_len"],))), BF)
    cache = on_chip(jax.eval_shape(lambda: net.init_kv_cache(
        _XL_SLOTS, _XL["seq_len"], page_size=_XL_PAGE,
        dtype=cache_dtype)))
    s = _XL_SLOTS
    if program == "step":
        def fn(cache, params, tok, active):
            return net.decode_step(params, cache, tok, active=active)
        args = [jax.ShapeDtypeStruct((s,), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((s,), jnp.bool_, sharding=one_chip)]
    else:
        def fn(cache, params, ids, plens, slots):
            return net.prefill(params, cache, ids, plens, slots)
        args = [jax.ShapeDtypeStruct((1, _XL["seq_len"]), jnp.int32,
                                     sharding=one_chip)] + \
            [jax.ShapeDtypeStruct((1,), jnp.int32,
                                  sharding=one_chip)] * 2
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        cache, params, *args).compile()
    return cache, compiled


def test_engine_step_keeps_the_last_tokens_on_the_chip(
        one_chip, as_on_the_chip):
    """The engine's own step program, as `_get_step` compiles it:
    the slots' last tokens go in as a device array and come out
    aliased in place, donated like the cache, beside the vector the
    host fetches, so the next step is dispatched with no host value
    of this one; the program's name is what the readers look for."""
    import re
    from analytics_zoo_tpu.pipeline.api.keras.layers.transformer \
        import TransformerLayer
    from analytics_zoo_tpu.pipeline.inference import GenerationEngine
    net = TransformerLayer(n_block=2, hidden_size=256, n_head=2,
                           seq_len=256, vocab=512, hidden_p_drop=0.0,
                           attn_p_drop=0.0, embed_p_drop=0.0)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(BF), net.build(jax.random.key(0), (256,)))
    eng = GenerationEngine(net, params, max_slots=8, max_context=256,
                           page_size=16, cache_dtype=BF)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    structs = on_chip((
        eng._abstract(eng.cache), eng._abstract(eng.params),
        eng._shape(8), eng._shape(8, dtype=np.bool_),
        eng._shape(8, dtype=np.float32), eng._abstract(eng._rng),
        eng._shape()))
    compiled = jax.jit(eng._step_fn, donate_argnums=(0, 2)).lower(
        *structs).compile()
    header = compiled.as_text().split("\n", 1)[0]
    assert header.split()[1].rstrip(",") == "jit__step_fn"
    n_cache = len(jax.tree_util.tree_leaves(eng.cache))
    n_params = len(jax.tree_util.tree_leaves(eng.params))
    # outputs: the cache's leaves, the last tokens, the fetched ones
    aliased = dict(re.findall(r"\{(\d+)\}: \((\d+),", header))
    assert aliased[str(n_cache)] == str(n_cache + n_params), header
    out = compiled.output_shardings
    assert len(jax.tree_util.tree_leaves(out)) == n_cache + 2
    assert "zoo_paged_decode" in compiled.as_text()


def _no_shape_leads_with(hlo, *dims):
    """No operand or result of the program has a shape whose leading
    dimensions are ``dims``, or their product (the same rows
    flattened)."""
    import re
    flat = int(np.prod(dims))
    lead = r"\[(?:%s|%d)[,\]]" % (",".join(map(str, dims)), flat)
    hits = sorted(set(re.findall(r"\w+" + lead + r"[^ ]*", hlo)))
    assert not hits, hits[:8]


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_generation_programs_leave_the_pools_in_place(
        one_chip, as_on_the_chip, program):
    """The device lays a K/V pool out row-major (a page is one
    contiguous block: rows are `heads * head_dim` padded to whole
    lane tiles, or the runtime picks a layout with the page axis
    minor-most), and neither the decode step nor a prefill copies,
    transposes or slices anything of a pool's or a layer slab's
    shape: the only operations that produce a pool are the two
    in-place scatters (the step's paged attention kernel reads the
    pools where they lie)."""
    import re
    cache, compiled = _xl_program(one_chip, program)
    hlo = compiled.as_text()
    pool = ",".join(map(str, cache.k_pages.shape))
    slab = ",".join(map(str, cache.k_pages.shape[1:]))
    assert cache.k_pages.shape[-1] % kvc.ROW_ALIGN == 0
    entry = hlo.split("entry_computation_layout={(", 1)[1]
    assert entry.startswith(f"bf16[{pool}]{{3,2,1,0:"), entry[:80]
    made = re.findall(
        r"= bf16\[(?:1,)?(?:%s|%s)\]\S* ([\w\-]+)\(" % (pool, slab),
        hlo)
    moved = [op for op in made if op in (
        "copy", "transpose", "dynamic-slice", "dynamic-update-slice")]
    assert not moved, moved
    # donated and written in place: output pools alias input pools
    assert "{0}: (0, {}, may-alias), {1}: (1, {}, may-alias)" in hlo


def test_gpt2xl_step_attends_from_the_pages(one_chip, as_on_the_chip):
    """The GPT-2-XL cell's decode step holds the paged attention
    kernel and no dense view of a layer's context: nothing of the
    gathered pages' shape (8 slots x 64 pages = 512 pages of 16 rows),
    of the view's (8 x 1024 rows of 1664) or of its head-split
    relayout (8 x 1024 x 1600)."""
    _, compiled = _xl_program(one_chip, "step")
    hlo = compiled.as_text()
    assert any("tpu_custom_call" in line and "zoo_paged_decode" in line
               for line in hlo.splitlines())
    for shape in ("8,1024,1600", "8,1024,1664", "512,16,1664",
                  "8,1024,25,64", "8,64,16,1664"):
        assert f"bf16[{shape}]" not in hlo, shape


def test_gpt2xl_int8_step_keeps_the_dense_view(one_chip,
                                               as_on_the_chip):
    """Int8 pools, with their scale pools, are not the kernel's: that
    step gathers its dense view as before."""
    _, compiled = _xl_program(one_chip, "step", cache_dtype=jnp.int8)
    hlo = compiled.as_text()
    assert "zoo_paged_decode" not in hlo
    assert "s8[512,16,1664]" in hlo or "s8[8,1024,1664]" in hlo


def test_gpt2xl_prefill_computes_the_admitted_row_only(one_chip):
    """The longest prefill program of the GPT-2-XL cell holds one
    prompt: nothing in it has `max_slots x bucket` rows, its
    temporaries are an eighth of the 5.14 GB that all 8 slots padded
    to 1024 took, and prompts that long still reach the flash kernel.
    (`bf16[393216,1664]` is in it and is no padding: 48 layers x 512
    pages x 16 rows, the pool as its in-place scatter sees it.)"""
    cache, compiled = _xl_program(one_chip, "prefill")
    hlo = compiled.as_text()
    _no_shape_leads_with(hlo, _XL_SLOTS, _XL["seq_len"])
    assert compiled.memory_analysis().temp_size_in_bytes < 0.75e9
    assert "zoo_flash_fwd" in hlo


# ---------------------------------------------------------------------
# DeepSeek-V2 as the benchmark's configuration holds it (one chip's
# share of four: 5 layers, 40 of 160 experts, 25600 rows of the
# vocabulary; every width as published): the decode step and the
# largest prefill bucket fit the chip beside the weights, and the one
# latent pool stays where it lies
# ---------------------------------------------------------------------

def _ds_config():
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "deepseek-v2-ep4.json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _ds_program(one_chip, program, bucket=None, lower_only=False):
    """("step" | "prefill") -> (cache shapes, weight bytes,
    compiled): the decode step over every slot, or a prefill program
    of the engine, ONE prompt row of ``bucket`` tokens (default: the
    longest, ``max_context``) addressed by slot."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import \
        deepseek_v2_decoder
    cfg = _ds_config()
    eng = cfg["engine"]
    first, end = cfg["held"]["experts"]
    # "auto" asks jax.devices(), the CPU here: name the kernel the
    # chip's "auto" takes from 1024 keys up
    net = deepseek_v2_decoder(
        dict(cfg, n_routed_experts=cfg["published"]["n_routed_experts"]),
        n_layer=cfg["n_layer"], experts_held=(first, end - first),
        attention_impl="xla" if bucket and bucket < 1024 else "flash")

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, dtype if dtype and a.dtype == F32 else a.dtype,
                sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: net.build(jax.random.key(0), (16,))), BF)
    s, ctx = eng["max_slots"], eng["max_context"]
    cache = on_chip(jax.eval_shape(lambda: net.init_kv_cache(
        s, ctx, page_size=eng["page_size"], dtype=BF)))
    if program == "step":
        def fn(cache, params, tok, active):
            return net.decode_step(params, cache, tok, active=active,
                                   stats=True)
        args = [jax.ShapeDtypeStruct((s,), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((s,), jnp.bool_, sharding=one_chip)]
    else:
        def fn(cache, params, ids, plens, slots):
            return net.prefill(params, cache, ids, plens, slots)
        args = [jax.ShapeDtypeStruct((1, bucket or ctx), jnp.int32,
                                     sharding=one_chip)] + \
            [jax.ShapeDtypeStruct((1,), jnp.int32,
                                  sharding=one_chip)] * 2
    lowered = jax.jit(fn, donate_argnums=(0,)).lower(
        cache, params, *args)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    return cache, weights, lowered if lower_only else \
        lowered.compile()


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_deepseek_v2_programs_fit_the_chip_and_leave_the_pool(
        one_chip, program):
    """At the published widths and the configuration's depth, slots
    and context: 10.33 GB of weights, the program's arguments,
    results and temporaries inside 15.75 GB; the latent pool
    (rows of 576 padded to 640) row-major, produced by nothing but
    its one in-place scatter, and aliased to its input."""
    import re
    cache, weights, compiled = _ds_program(one_chip, program)
    assert abs(weights - 10.33e9) < 0.005e9, weights
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes - \
        mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert held < _V5E_BYTES, held
    hlo = compiled.as_text()
    assert cache.pages.shape == (5, 2048, 16, 640)
    pool = ",".join(map(str, cache.pages.shape))
    slab = ",".join(map(str, cache.pages.shape[1:]))
    entry = hlo.split("entry_computation_layout={(", 1)[1]
    assert entry.startswith(f"bf16[{pool}]{{3,2,1,0:"), entry[:80]
    made = re.findall(
        r"= bf16\[(?:1,)?(?:%s|%s)\]\S* ([\w\-]+)\(" % (pool, slab),
        hlo)
    moved = [op for op in made if op in (
        "copy", "transpose", "dynamic-slice", "dynamic-update-slice")]
    assert not moved, moved
    assert "{0}: (0, {}, may-alias)" in hlo
    # the routed experts are the grouped product's kernel: an expert
    # no token chose is not read
    assert "zoo_grouped_matmul" in hlo
    if program == "prefill":
        assert "zoo_flash_fwd" in hlo
        # one prompt row: nothing has `max_slots x bucket` rows, and
        # the temporaries are a quarter of the 2.54 GB that 16 slots
        # padded to 2048 took
        eng = _ds_config()["engine"]
        _no_shape_leads_with(hlo, eng["max_slots"], eng["max_context"])
        assert mem.temp_size_in_bytes < 0.8e9, mem.temp_size_in_bytes


def test_deepseek_v2_prefill_ladder_never_looks_like_a_decode_step(
        one_chip):
    """A decode step's grouped products have `max_slots x experts a
    token` = 96 rows (the rows by which `benchmark/reduce/moe.py` put
    the compiler's own grouped products back under their scope; the
    kernel keeps its scope). A prefill program holds one prompt, so
    its grouped products have `bucket x 6` rows: no bucket of the
    engine's ladder may be as short as the engine has slots. Every
    program of the ladder as lowered, and the shortest as the v5e
    compiler names it."""
    import re

    from analytics_zoo_tpu.pipeline.inference.generation import \
        prompt_ladder
    cfg = _ds_config()
    eng, per_token = cfg["engine"], cfg["num_experts_per_tok"]
    decode_rows = eng["max_slots"] * per_token
    assert decode_rows == 96
    ladder = prompt_ladder(eng["max_context"])
    assert ladder[0] >= 32 and ladder[-1] == eng["max_context"]
    for bucket in ladder:
        _, _, lowered = _ds_program(one_chip, "prefill", bucket=bucket,
                                    lower_only=True)
        rows = set(re.findall(_GROUPED_LOWERED, lowered.as_text()))
        assert rows == {str(bucket * per_token)}, (bucket, rows)
        assert bucket * per_token != decode_rows
    compiled = _ds_program(one_chip, "prefill", bucket=ladder[0])[2]
    named = set(re.findall(_GROUPED_COMPILED, compiled.as_text()))
    assert named == {str(ladder[0] * per_token)}, named


def _config(name):
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        name + ".json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_dots3_note_programs_fit_the_chip_and_leave_the_pools(
        one_chip, as_on_the_chip, program):
    """At the published widths and the configuration's depth, slots,
    context and chunk: 10.02 GB of weights, the program's arguments,
    results and temporaries inside 11.95 GB (the step) and 12.7 GB
    (the chunk, as the chip runs it: its attention the chunk kernel)
    of the chip's 15.75; the three pools (context
    rows of 576 padded to 640, index keys of 128, window rows of 1088
    padded to 1152 in a ring of 161 pages a slot) row-major, aliased
    to their inputs, and nothing of a pool's shape copied, transposed
    or sliced. The step's view of every slot's whole index context is
    the size of a layer's index slab by construction (8 slots x 2048
    pages), so only the whole pool is looked for there."""
    import re

    from analytics_zoo_tpu.pipeline.api.keras.layers import \
        dots3_note_decoder
    cfg = _config("dots3-note-prev-ep8")
    eng = cfg["engine"]
    first, end = cfg["held"]["experts"]
    net = dots3_note_decoder(
        dict(cfg, n_routed_experts=cfg["published"]["n_routed_experts"]),
        n_layer=cfg["n_layer"], experts_held=(first, end - first))

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, dtype if dtype and a.dtype == F32 else a.dtype,
                sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: net.build(jax.random.key(0), (16,))), BF)
    s = eng["max_slots"]
    cache = on_chip(jax.eval_shape(lambda: net.init_kv_cache(
        s, eng["max_context"], page_size=eng["page_size"], dtype=BF,
        max_chunk=eng["prefill_chunk"])))
    i32 = lambda *d: jax.ShapeDtypeStruct(d, jnp.int32,
                                          sharding=one_chip)
    if program == "step":
        def fn(cache, params, tok, active):
            return net.decode_step(params, cache, tok, active=active,
                                   stats=True)
        args = [i32(s), jax.ShapeDtypeStruct((s,), jnp.bool_,
                                             sharding=one_chip)]
    else:
        def fn(cache, params, ids, starts, n_new, slots):
            return net.forward_chunk(params, cache, ids, starts, n_new,
                                     slots=slots, stats=True)
        args = [i32(1, eng["prefill_chunk"]), i32(1), i32(1), i32(1)]
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        cache, params, *args).compile()
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 10.02e9) < 0.005e9, weights
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes - \
        mem.alias_size_in_bytes + mem.temp_size_in_bytes
    # 11.86 GB the step, 12.67 GB the chunk (12.75 GB with the XLA
    # body and its f32 scores; 1.29 GB of temporaries against 1.38,
    # my compiles, PR 39)
    assert held < (11.95e9 if program == "step" else 12.7e9), held
    assert cache.pages.shape == (3, 16384, 16, 640)
    assert cache.index.shape == (3, 16384, 16, 128)
    assert cache.window.shape == (3, 8 * 161, 16, 1152)
    hlo = compiled.as_text()
    entry = hlo.split("entry_computation_layout={(", 1)[1]
    assert entry.startswith(
        "bf16[3,16384,16,640]{3,2,1,0:"), entry[:80]
    shapes = []
    for pool, slab in ((cache.pages, True), (cache.index, False),
                       (cache.window, True)):
        shapes.append(",".join(map(str, pool.shape)))
        if slab:
            shapes.append(",".join(map(str, pool.shape[1:])))
        assert "bf16[%s]{3,2,1,0:" % shapes[-1 - slab] in entry
    made = re.findall(
        r"= bf16\[(?:1,)?(?:%s)\]\S* ([\w\-]+)\(" % "|".join(shapes),
        hlo)
    moved = [op for op in made if op in (
        "copy", "transpose", "dynamic-slice", "dynamic-update-slice")]
    assert not moved, moved
    # donated and written in place: all five leaves of the cache
    for leaf in range(5):
        assert "{%d}: (%d, {}, may-alias)" % (leaf, leaf) in hlo
    # the routed experts are the grouped product's kernel, of 8 slots
    # x 8 experts a token in a step, 2048 x 8 in a chunk
    rows = set(re.findall(_GROUPED_COMPILED, hlo))
    want = s * cfg["num_experts_per_tok"] if program == "step" \
        else eng["prefill_chunk"] * cfg["num_experts_per_tok"]
    assert rows == {str(want)}, rows
    if program == "chunk":
        # the six layers' attention is the chunk kernel, and the
        # scores of a block of queries (16 heads x 256 queries x the
        # keys of a bucket or of the ring) exist nowhere
        assert "zoo_flash_chunk" in hlo
        scores = re.findall(r"f32\[(?:1,)*16,256,\d{4,}\]", hlo)
        assert not scores, sorted(set(scores))


# -- MiMo-V2-Flash: grouped-query rows of two geometries -----------------

def _mimo(one_chip):
    from analytics_zoo_tpu.pipeline.api.keras.layers import \
        mimo_v2_flash_decoder
    cfg = _config("mimo-v2-flash-ep16")
    eng = cfg["engine"]
    first, end = cfg["held"]["experts"]
    net = mimo_v2_flash_decoder(
        dict(cfg, n_routed_experts=cfg["published"]["n_routed_experts"]),
        n_layer=cfg["n_layer"], experts_held=(first, end - first),
        vocab=cfg["vocab_size"])

    built = jax.eval_shape(lambda: net.build(jax.random.key(0), (16,)))
    # matrices and norm gains in bfloat16; the routers' selection
    # biases and the sink biases stay float32, as the weights' maker
    # leaves them
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: jax.ShapeDtypeStruct(
            a.shape, F32 if any(
                getattr(k, "key", None) in ("router_bias", "sink")
                for k in path) else BF, sharding=one_chip), built)
    cache = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda: net.init_kv_cache(
            eng["max_slots"], eng["max_context"],
            page_size=eng["page_size"], dtype=BF,
            max_chunk=eng["prefill_chunk"])))
    return cfg, net, params, cache


def _mimo_program(one_chip, program, bucket=None, lower_only=False):
    cfg, net, params, cache = _mimo(one_chip)
    eng = cfg["engine"]
    s = eng["max_slots"]
    i32 = lambda *d: jax.ShapeDtypeStruct(d, jnp.int32,
                                          sharding=one_chip)
    if program == "step":
        def fn(cache, params, tok, active):
            return net.decode_step(params, cache, tok, active=active,
                                   stats=True)
        args = [i32(s), jax.ShapeDtypeStruct((s,), jnp.bool_,
                                             sharding=one_chip)]
    elif program == "chunk":
        def fn(cache, params, ids, starts, n_new, slots):
            return net.forward_chunk(params, cache, ids, starts, n_new,
                                     slots=slots, stats=True)
        args = [i32(1, eng["prefill_chunk"]), i32(1), i32(1), i32(1)]
    else:
        def fn(cache, params, ids, plens, slots):
            return net.prefill(params, cache, ids, plens, slots,
                               stats=True)
        args = [i32(1, bucket), i32(1), i32(1)]
    lowered = jax.jit(fn, donate_argnums=(0,)).lower(
        cache, params, *args)
    return cfg, params, cache, \
        lowered if lower_only else lowered.compile()


@pytest.mark.parametrize("program, bucket", [
    ("step", None), ("chunk", None), ("prefill", 2048),
    ("prefill", 64)])
def test_mimo_v2_flash_programs_fit_the_chip_and_leave_the_pools(
        one_chip, as_on_the_chip, program, bucket):
    """At the published widths and the configuration's depth, slots,
    context and chunk: 6.86 GB of weights, the program's arguments,
    results and temporaries inside 15.75 GB; the context pool (two
    full layers' rows of 4 x (192 + 128) = 1280) and the ring (five
    sliding layers' rows of 8 x 320 = 2560, 137 pages a slot)
    row-major, aliased to their inputs, nothing of a pool's shape
    copied, transposed or sliced; the step reads both through the
    paged kernel and holds no dense view of a slot's context."""
    import re
    cfg, params, cache, compiled = _mimo_program(one_chip, program,
                                                 bucket)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 6.86e9) < 0.005e9, weights
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes - \
        mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert held < _V5E_BYTES, held
    assert cache.pages.shape == (2, 32768, 16, 1280)
    assert cache.window.shape == (5, 16 * 137, 16, 2560)
    assert cache.index is None
    hlo = compiled.as_text()
    entry = hlo.split("entry_computation_layout={(", 1)[1]
    assert entry.startswith(
        "bf16[2,32768,16,1280]{3,2,1,0:"), entry[:80]
    shapes = []
    for pool in (cache.pages, cache.window):
        shapes.append(",".join(map(str, pool.shape)))
        shapes.append(",".join(map(str, pool.shape[1:])))
        assert "bf16[%s]{3,2,1,0:" % shapes[-2] in entry
    made = re.findall(
        r"= bf16\[(?:1,)?(?:%s)\]\S* ([\w\-]+)\(" % "|".join(shapes),
        hlo)
    moved = [op for op in made if op in (
        "copy", "transpose", "dynamic-slice", "dynamic-update-slice")]
    assert not moved, moved
    # donated and written in place: the four leaves of the cache
    for leaf in range(4):
        assert "{%d}: (%d, {}, may-alias)" % (leaf, leaf) in hlo
    eng = cfg["engine"]
    rows = set(re.findall(_GROUPED_COMPILED, hlo))
    tokens = {"step": eng["max_slots"], "chunk": eng["prefill_chunk"],
              "prefill": bucket}[program]
    assert rows == {str(tokens * cfg["num_experts_per_tok"])}, rows
    if program == "step":
        assert hlo.count("zoo_paged_gqa_decode") >= 7
        # no slot's whole context as a view: 16 x 32768 positions
        _no_shape_leads_with(hlo, eng["max_slots"], eng["max_context"])
        assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes
    if program == "prefill" and bucket == 2048:
        assert "zoo_flash_fwd" in hlo


def test_mimo_v2_flash_prefill_ladder_never_looks_like_a_decode_step(
        one_chip):
    """A decode step's grouped products have 16 slots x 8 experts
    a token = 128 rows (by which `benchmark/reduce/moe.py` told the
    compiler's own grouped products apart), which a 16-token prompt
    bucket would give too. The engine's ladder starts at 32, and no
    bucket up to the chunk has 128 rows."""
    import re

    from analytics_zoo_tpu.pipeline.inference.generation import \
        prompt_ladder
    cfg = _config("mimo-v2-flash-ep16")
    eng, per_token = cfg["engine"], cfg["num_experts_per_tok"]
    decode_rows = eng["max_slots"] * per_token
    assert decode_rows == 128
    ladder = [b for b in prompt_ladder(eng["max_context"])
              if b <= eng["prefill_chunk"]]
    assert ladder[0] >= 32 and ladder[-1] == eng["prefill_chunk"]
    for bucket in ladder:
        assert bucket * per_token != decode_rows
    lowered = _mimo_program(one_chip, "prefill", bucket=ladder[0],
                            lower_only=True)[3]
    rows = set(re.findall(_GROUPED_LOWERED, lowered.as_text()))
    assert rows == {str(ladder[0] * per_token)}, rows


# -- Nemotron-H: a state a slot beside the pages -------------------------

def _nemotron_program(one_chip, program):
    from analytics_zoo_tpu.pipeline.api.keras.layers import \
        nemotron_h_decoder
    cfg = _config("nemotron3-nano-ep8")
    eng = cfg["engine"]
    first, end = cfg["held"]["experts"]
    net = nemotron_h_decoder(
        dict(cfg, n_routed_experts=cfg["published"]["n_routed_experts"]),
        n_layer=cfg["n_layer"], experts_held=(first, end - first),
        vocab=cfg["vocab_size"])
    built = jax.eval_shape(lambda: net.build(jax.random.key(0), (16,)))
    # matrices and norm gains in bfloat16; the routers' selection
    # biases and the scan's dt_bias, A_log and D stay float32, as the
    # weights' maker leaves them
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: jax.ShapeDtypeStruct(
            a.shape, F32 if any(
                getattr(k, "key", None) in (
                    "router_bias", "dt_bias", "A_log", "D")
                for k in path) else BF, sharding=one_chip), built)
    s = eng["max_slots"]
    cache = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda: net.init_kv_cache(
            s, eng["max_context"], page_size=eng["page_size"], dtype=BF,
            max_chunk=eng["prefill_chunk"])))
    i32 = lambda *d: jax.ShapeDtypeStruct(d, jnp.int32,
                                          sharding=one_chip)
    if program == "step":
        def fn(cache, params, tok, active):
            return net.decode_step(params, cache, tok, active=active,
                                   stats=True)
        args = [i32(s), jax.ShapeDtypeStruct((s,), jnp.bool_,
                                             sharding=one_chip)]
    else:
        def fn(cache, params, ids, starts, n_new, slots):
            return net.forward_chunk(params, cache, ids, starts, n_new,
                                     slots=slots, stats=True)
        args = [i32(1, eng["prefill_chunk"]), i32(1), i32(1), i32(1)]
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        cache, params, *args).compile()
    return cfg, params, cache, compiled


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_nemotron_h_programs_fit_the_chip_and_update_the_state_in_place(
        one_chip, as_on_the_chip, program):
    """At the published widths and the configuration's depth, slots,
    context and chunk: 5.21 GB of weights, the program's arguments,
    results and temporaries inside the chip; the 12 Mamba layers'
    state (96 slots x 64 x 64 x 128 float32, 2.4 GB, and the conv
    tails) and the 3 attention layers' pool (rows of 2 x 256) donated
    and aliased to their inputs, and no copy of the state made: a step
    reads and writes each layer's slab in place. The step reads the
    pool through the paged kernel."""
    import re
    cfg, params, cache, compiled = _nemotron_program(one_chip, program)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 5.21e9) < 0.005e9, weights
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes - \
        mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert held < _V5E_BYTES, held
    assert cache.pages.shape == (3, 36864, 16, 512)
    assert cache.ssm.shape == (12, 96, 64, 64, 128)
    assert cache.conv.shape == (12, 96, 3, 6144)
    assert cache.window is None and cache.index is None
    hlo = compiled.as_text()
    # donated and written in place: the five leaves of the cache
    # (pages, table, lengths, conv, ssm)
    for leaf in range(5):
        assert "{%d}: (%d, {}, may-alias)" % (leaf, leaf) in hlo
    # no second state: nothing of the whole state's shape is made
    # but in place, and no temporary holds a layer's slab twice
    made = re.findall(r"= f32\[12,96,64,64,128\]\S* ([\w\-]+)\(", hlo)
    assert not [op for op in made if op in ("copy", "transpose")], made
    if program == "step":
        assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes
        assert hlo.count("zoo_paged_gqa_decode") >= 3
    # the grouped product's kernel reads the 16 held experts where they
    # lie (the up weights' last dim, 1856, is no whole lane tile: the
    # compiler keeps them 2688-minor, and the kernel takes that view)
    made = re.findall(r"= bf16\[16,(?:2688,1856|1856,2688)\]\S* "
                      r"([\w\-]+)\(", hlo)
    assert not [op for op in made if op in ("copy", "transpose")], made
    rows = set(re.findall(_GROUPED_COMPILED, hlo))
    tokens = cfg["engine"]["max_slots"] if program == "step" \
        else cfg["engine"]["prefill_chunk"]
    assert rows == {str(tokens * cfg["num_experts_per_tok"])}, rows
