"""Autoregressive decode fast path: compiled generate loops must be
EXACT against naive uncached references (transformer + seq2seq), the
paged-cache serving engine must match the whole-loop path token for
token under continuous batching with staggered admission, and the
warmed decode loop must never compile in steady state. Tier-1 fast.
"""

import json
import time

import numpy as np
import pytest

from analytics_zoo_tpu import init_nncontext
from analytics_zoo_tpu.common.observability import reset_metrics
from analytics_zoo_tpu.pipeline.inference import (
    ContinuousBatcher, GenerationEngine, InferenceModel,
    InferenceServer)
from analytics_zoo_tpu.pipeline.inference.serving import (
    handle_generate)

SEQ, VOCAB = 32, 61


@pytest.fixture(autouse=True)
def _fresh_metrics():
    reset_metrics()
    yield
    reset_metrics()


def _toy_transformer(cache_dtype=None):
    init_nncontext(seed=0)
    import jax
    from analytics_zoo_tpu.pipeline.api.keras.layers.transformer \
        import TransformerLayer
    net = TransformerLayer(n_block=2, hidden_size=32, n_head=2,
                           seq_len=SEQ, vocab=VOCAB,
                           hidden_p_drop=0.0, attn_p_drop=0.0,
                           embed_p_drop=0.0)
    params = net.build(jax.random.key(0), (SEQ,))
    return net, params


def _naive_greedy(net, params, prompt, max_new):
    """Uncached greedy reference: re-forward the WHOLE prefix for
    every new token; argmax the weight-tied logits."""
    import jax.numpy as jnp
    ids = list(prompt)
    out = []
    for _ in range(max_new):
        h = net.call(params, jnp.asarray([ids], jnp.int32),
                     training=False)
        logits = h[0, len(ids) - 1] @ params["tok_embed"].T
        tok = int(jnp.argmax(logits))
        out.append(tok)
        ids.append(tok)
    return out


# -- model layer: the compiled loop is exact ---------------------------------

def test_transformer_generate_matches_naive_reference():
    net, params = _toy_transformer()
    rs = np.random.RandomState(0)
    plens = [3, 5, 2]  # padded slots: one (S, 5) batch, mixed lens
    max_new = 6
    prompts = [rs.randint(1, VOCAB, size=n).tolist() for n in plens]
    tp = max(plens)
    ids = np.zeros((len(plens), tp), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    buf, lens = net.generate(params, ids,
                             prompt_lens=np.asarray(plens, np.int32),
                             max_new_tokens=max_new)
    buf, lens = np.asarray(buf), np.asarray(lens)
    assert lens.tolist() == [n + max_new for n in plens]
    for i, p in enumerate(prompts):
        ref = _naive_greedy(net, params, p, max_new)
        got = buf[i, plens[i]:lens[i]].tolist()
        assert got == ref, (i, got, ref)
        # the prompt itself is preserved, left-compacted
        assert buf[i, :plens[i]].tolist() == p


def test_transformer_generate_eos_stops_slot():
    net, params = _toy_transformer()
    prompt = [5, 9, 2]
    full = _naive_greedy(net, params, prompt, 8)
    eos = full[3]  # stop at this token's FIRST occurrence
    k = full.index(eos)
    buf, lens = net.generate(
        params, np.asarray([prompt], np.int32),
        max_new_tokens=8, eos_id=eos)
    got = np.asarray(buf)[0, 3:int(np.asarray(lens)[0])].tolist()
    assert got == full[:k + 1]  # eos included, nothing after


def test_transformer_generate_bf16_cache_tolerance():
    import jax.numpy as jnp
    net, params = _toy_transformer()
    prompt = [7, 3, 11, 2]
    # bf16 KV storage perturbs logits only within bf16 noise...
    cache32 = net.init_kv_cache(1, 16, page_size=8)
    cache16 = net.init_kv_cache(1, 16, page_size=8,
                                dtype=jnp.bfloat16)
    ids = jnp.asarray([prompt], jnp.int32)
    pl = jnp.asarray([len(prompt)], jnp.int32)
    _, lg32 = net.prefill(params, cache32, ids, pl)
    _, lg16 = net.prefill(params, cache16, ids, pl)
    np.testing.assert_allclose(
        np.asarray(lg16, np.float32), np.asarray(lg32, np.float32),
        atol=0.15, rtol=0.05)
    # ...and this model's greedy argmax margins absorb it: the bf16
    # cache generates the identical token sequence
    ref = _naive_greedy(net, params, prompt, 6)
    buf, lens = net.generate(params, jnp.asarray([prompt], jnp.int32),
                             max_new_tokens=6,
                             cache_dtype=jnp.bfloat16)
    got = np.asarray(buf)[0, 4:int(np.asarray(lens)[0])].tolist()
    assert got == ref


def test_seq2seq_generate_matches_host_loop():
    from analytics_zoo_tpu.models.seq2seq import (
        Bridge, RNNDecoder, RNNEncoder, Seq2seq)
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    init_nncontext(seed=0)
    rs = np.random.RandomState(1)
    b, t_in, f = 2, 4, 6
    s2s = Seq2seq(encoder=RNNEncoder("lstm", 1, 8),
                  decoder=RNNDecoder("lstm", 1, 8),
                  input_shape=(t_in, f), output_shape=(t_in, f),
                  bridge=Bridge("dense"),
                  generator=Dense(f, name="generator"))
    s2s.compile(optimizer="sgd", loss="mse")
    est = s2s.model.estimator
    est._ensure_initialized()
    params, net = est.params, s2s.model
    enc = rs.randn(b, t_in, f).astype(np.float32)
    start = np.ones((f,), np.float32)
    max_new = 5
    import jax.numpy as jnp
    buf, counts = net.generate(params, jnp.asarray(enc), start,
                               max_new)
    buf = np.asarray(buf)
    assert np.asarray(counts).tolist() == [1 + max_new] * b
    # host-loop reference: encode once, step the decoder by hand
    carries = net.encode(params, jnp.asarray(enc))
    last = jnp.broadcast_to(jnp.asarray(start), (b, f))
    ref = [np.asarray(last)]
    for _ in range(max_new):
        carries, y = net.decode_step(params, carries, last)
        ref.append(np.asarray(y))
        last = y
    np.testing.assert_allclose(buf, np.stack(ref, axis=1),
                               rtol=1e-5, atol=1e-6)


def test_seq2seq_generate_tokens_greedy_matches_host_loop():
    from analytics_zoo_tpu.models.seq2seq import (
        Bridge, RNNDecoder, RNNEncoder, Seq2seq)
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    init_nncontext(seed=0)
    rs = np.random.RandomState(2)
    b, t_in, v = 2, 3, 7
    s2s = Seq2seq(encoder=RNNEncoder("gru", 1, 8),
                  decoder=RNNDecoder("gru", 1, 8),
                  input_shape=(t_in, v), output_shape=(t_in, v),
                  bridge=Bridge("dense"),
                  generator=Dense(v, activation="softmax",
                                  name="generator"))
    s2s.compile(optimizer="sgd", loss="mse")
    est = s2s.model.estimator
    est._ensure_initialized()
    params, net = est.params, s2s.model
    enc = rs.randn(b, t_in, v).astype(np.float32)
    max_new = 6
    import jax
    import jax.numpy as jnp
    buf, counts = net.generate_tokens(params, jnp.asarray(enc), 1,
                                      max_new)
    buf = np.asarray(buf)
    assert buf[:, 0].tolist() == [1, 1]
    carries = net.encode(params, jnp.asarray(enc))
    last = jnp.full((b,), 1, jnp.int32)
    ref = [np.asarray(last)]
    for _ in range(max_new):
        x = jax.nn.one_hot(last, v, dtype=jnp.float32)
        carries, y = net.decode_step(params, carries, x)
        last = jnp.argmax(y, axis=-1).astype(jnp.int32)
        ref.append(np.asarray(last))
    assert buf.tolist() == np.stack(ref, axis=1).tolist()


# -- ops layer: decode attention kernel conformance --------------------------

def test_flash_decode_attention_matches_dense(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    from analytics_zoo_tpu.ops.flash_attention import (
        flash_decode_attention)
    rs = np.random.RandomState(3)
    s, t, h, d = 3, 128, 2, 64
    q = rs.randn(s, h, d).astype(np.float32)
    k = rs.randn(s, t, h, d).astype(np.float32)
    v = rs.randn(s, t, h, d).astype(np.float32)
    seq_lens = np.asarray([17, 128, 1], np.int32)
    key_mask = (np.arange(t)[None, :]
                < seq_lens[:, None]).astype(np.float32)
    scale = 1.0 / d ** 0.5
    out = np.asarray(flash_decode_attention(
        q, k, v, key_mask, scale, interpret=True))
    # dense reference: masked softmax over the valid prefix
    logits = np.einsum("shd,sthd->sht", q, k) * scale
    logits = np.where(key_mask[:, None, :] > 0, logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("sht,sthd->shd", p, v)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# -- serving engine: paged cache + slot stepping -----------------------------

def _engine(**kw):
    net, params = _toy_transformer()
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_context", SEQ)
    kw.setdefault("page_size", 8)
    return GenerationEngine(net, params, **kw)


def test_engine_admit_step_release_matches_whole_loop():
    eng = _engine()
    prompt = [4, 19, 7]
    max_new = 6
    ref = [int(t) for t in
           eng.generate(prompt, max_new_tokens=max_new)[0]]
    (slot, first), = eng.admit([(prompt, max_new, 0.0)])
    got = [first]
    active = np.zeros((eng.max_slots,), np.bool_)
    active[slot] = True
    while len(got) < max_new:
        got.append(int(eng.step(active)[slot]))
    eng.release(slot)
    assert got == ref
    assert eng.slots_active == 0


def test_engine_page_accounting_and_admission_gate():
    eng = _engine()
    total = eng.allocator.max_pages
    assert eng.free_pages == total
    # worst-case reservation up front: ceil((3 + 12) / 8) = 2 pages
    (slot, _), = eng.admit([([1, 2, 3], 12, 0.0)])
    assert eng.free_pages == total - 2
    assert eng.slots_active == 1
    eng.release(slot)
    assert eng.free_pages == total
    # a prompt longer than the cache window is rejected up front
    with pytest.raises(ValueError):
        eng.admit([(list(range(1, SEQ + 6)), 1, 0.0)])
    # all slots occupied -> the admission gate closes
    admitted = eng.admit([([i + 1], 2, 0.0)
                          for i in range(eng.max_slots)])
    assert not eng.can_admit(1, 1)
    for slot, _ in admitted:
        eng.release(slot)
    assert eng.can_admit(1, 1)


def test_continuous_batching_exact_with_staggered_admission():
    eng = _engine(max_slots=2)  # 2 slots, 5 requests: forced churn
    rs = np.random.RandomState(4)
    jobs = [(rs.randint(1, VOCAB, size=n).tolist(), m)
            for n, m in [(3, 6), (7, 4), (2, 8), (5, 5), (4, 7)]]
    # references BEFORE the loop thread owns the engine (the engine
    # is single-driver; generate uses a separate fresh-cache path)
    refs = [[int(t) for t in eng.generate(p, max_new_tokens=m)[0]]
            for p, m in jobs]
    cb = ContinuousBatcher(eng, queue_depth=16).start()
    try:
        # staggered: the first two occupy both slots; the rest queue
        # and are admitted as neighbours retire mid-decode
        futs = []
        for i, (p, m) in enumerate(jobs):
            futs.append(cb.submit(p, max_new_tokens=m))
            if i < 2:
                time.sleep(0.01)
        outs = [[int(t) for t in f.result(timeout=60)]
                for f in futs]
    finally:
        cb.stop()
    assert outs == refs  # admission churn never perturbs neighbours
    assert eng.slots_active == 0
    assert eng.free_pages == eng.allocator.max_pages


def test_continuous_batcher_queue_full_and_stop_fails_pending():
    from analytics_zoo_tpu.pipeline.inference.batching import (
        QueueFullError)
    eng = _engine(max_slots=2)
    cb = ContinuousBatcher(eng, queue_depth=2)  # NOT started
    cb.submit([1, 2], max_new_tokens=4)
    f2 = cb.submit([3], max_new_tokens=4)
    with pytest.raises(QueueFullError):
        cb.submit([4], max_new_tokens=4)
    cb.stop()
    with pytest.raises(RuntimeError):
        f2.result(timeout=5)


# -- the headline guarantee: zero compiles after warm-up ---------------------

def test_no_steady_state_compiles_across_varied_lengths():
    from jax import monitoring

    eng = _engine()
    rs = np.random.RandomState(5)
    compiles = []
    armed = [False]

    def listener(name, dur, **kw):
        if armed[0] and name.endswith("backend_compile_duration"):
            compiles.append(name)

    monitoring.register_event_duration_secs_listener(listener)
    cb = ContinuousBatcher(eng, queue_depth=32)
    try:
        cb.start()  # warm-up: step + every prompt bucket, AOT
        assert eng.stats()["warmed_programs"] == \
            1 + len(eng.prompt_buckets)
        armed[0] = True
        # staggered traffic across every bucket and varied budgets
        futs = []
        for n, m in [(1, 3), (3, 5), (2, 4), (8, 6), (15, 2),
                     (31, 3), (5, 9), (12, 1), (7, 7)]:
            futs.append(cb.submit(
                rs.randint(1, VOCAB, size=n).tolist(),
                max_new_tokens=m))
            time.sleep(0.002)
        for f, (_, m) in zip(futs, [(1, 3), (3, 5), (2, 4), (8, 6),
                                    (15, 2), (31, 3), (5, 9),
                                    (12, 1), (7, 7)]):
            assert len(f.result(timeout=60)) == m
        armed[0] = False
        assert compiles == [], (
            f"steady-state decode compiled {len(compiles)} times "
            f"across the staggered varied-length soak")
    finally:
        armed[0] = False
        cb.stop()


# -- serving layer: the /generate contract -----------------------------------

def _loaded_generator():
    net, params = _toy_transformer()
    im = InferenceModel()
    im.load_generator(net, params, max_slots=2, max_context=SEQ,
                      page_size=8)
    return im


def test_handle_generate_contract():
    im = _loaded_generator()
    prompt = [3, 14, 8]
    ref = [int(t) for t in
           im.generate(prompt, max_new_tokens=5)[0]]
    status, out = handle_generate(im, json.dumps(
        {"prompt": prompt, "max_new_tokens": 5}).encode())
    assert status == 200 and out["tokens"] == ref
    # batch form mirrors the request's shape
    status, out = handle_generate(im, json.dumps(
        {"prompts": [prompt, [9]], "max_new_tokens": 3}).encode())
    assert status == 200
    assert len(out["tokens"]) == 2
    assert out["tokens"][0] == ref[:3]
    # exactly one of prompt/prompts
    for bad in ({}, {"prompt": [1], "prompts": [[1]]}):
        status, out = handle_generate(im, json.dumps(bad).encode())
        assert status == 400, out
    status, out = handle_generate(im, b"not json")
    assert status == 400
    # no generator loaded -> 501, and the model raises eagerly too
    status, out = handle_generate(InferenceModel(), json.dumps(
        {"prompt": [1]}).encode())
    assert status == 501
    with pytest.raises(RuntimeError, match="no generator"):
        InferenceModel().generate([1, 2])


# -- capacity levers: chunked prefill, int8 KV cache, speculation ------------

def _toy_drafter():
    """A smaller stack sharing the vocabulary, differently
    initialized: agrees with the target often enough to accept
    sometimes, rarely enough to exercise rejection + resample."""
    init_nncontext(seed=0)
    import jax
    from analytics_zoo_tpu.pipeline.api.keras.layers.transformer \
        import TransformerLayer
    net = TransformerLayer(n_block=1, hidden_size=16, n_head=2,
                           seq_len=SEQ, vocab=VOCAB,
                           hidden_p_drop=0.0, attn_p_drop=0.0,
                           embed_p_drop=0.0)
    params = net.build(jax.random.key(7), (SEQ,))
    return net, params


def _drive_to_completion(eng, slot, first, prompt_len, max_new):
    """Finish one admitted request by hand: speculative rounds while
    the k-token window fits the reservation, regular steps for the
    tail (the batcher's eligibility gate, inlined)."""
    got = [first]
    active = np.zeros((eng.max_slots,), np.bool_)
    active[slot] = True
    while len(got) < max_new:
        window = prompt_len + len(got) - 1 + eng.spec_k
        budget = min(prompt_len + max_new, eng.max_context)
        if eng.spec_k > 0 and window <= budget:
            out, n_emit = eng.spec_step(active)
            got.extend(int(t) for t in out[slot, :n_emit[slot]])
        else:
            got.append(int(eng.step(active)[slot]))
    return got[:max_new]


def test_resolve_kv_dtype(monkeypatch):
    import jax.numpy as jnp
    from analytics_zoo_tpu.pipeline.inference.generation import (
        resolve_kv_dtype)
    assert resolve_kv_dtype("f32") == jnp.float32
    assert resolve_kv_dtype("bfloat16") == jnp.bfloat16
    assert resolve_kv_dtype("int8") == jnp.int8
    monkeypatch.setenv("ZOO_TPU_KV_DTYPE", "bf16")
    assert resolve_kv_dtype() == jnp.bfloat16
    monkeypatch.setenv("ZOO_TPU_KV_DTYPE", "fp4")
    with pytest.raises(ValueError, match="fp4"):
        resolve_kv_dtype()


def test_prefill_step_runs_one_chunk_program_in_turn():
    """However many prompts are mid-prefill, a `prefill_step` is ONE
    chunk program: the slots take turns in the order they were
    admitted, so a decode iteration waits for one chunk and a short
    prompt behind a long one is not kept waiting for all of it."""
    eng = _engine(prefill_chunk=4)
    rs = np.random.RandomState(9)
    long_p = rs.randint(1, VOCAB, size=11).tolist()   # 3 chunks
    short_p = rs.randint(1, VOCAB, size=6).tolist()   # 2 chunks
    want = {tuple(p): int(eng.generate(p, max_new_tokens=1)[0][0])
            for p in (long_p, short_p)}
    s0, s1 = eng.admit_partial([(long_p, 2, 0.0), (short_p, 2, 0.0)])
    work, firsts = [], []
    while eng.prefilling_slots:
        firsts += eng.prefill_step()
        work.append(eng.chunk_work)
    assert work == [(s0, 0, 4), (s1, 0, 4), (s0, 4, 4), (s1, 4, 2),
                    (s0, 8, 3)]
    assert firsts == [(s1, want[tuple(short_p)]),
                      (s0, want[tuple(long_p)])]
    assert eng.prefill_step() == [] and eng.chunk_work is None
    eng.release(s0)
    eng.release(s1)


def test_chunked_prefill_engine_exact_and_cancel_reclaims():
    """Chunk-at-a-time prompt writes produce the identical token
    stream, and cancelling one slot mid-prefill neither perturbs its
    neighbour nor leaks pages."""
    eng = _engine(prefill_chunk=4)
    rs = np.random.RandomState(8)
    prompt = rs.randint(1, VOCAB, size=11).tolist()  # 3 chunks
    other = rs.randint(1, VOCAB, size=6).tolist()    # 2 chunks
    max_new = 5
    ref = [int(t) for t in
           eng.generate(prompt, max_new_tokens=max_new)[0]]
    total = eng.allocator.max_pages
    s0, s1 = eng.admit_partial([(prompt, max_new, 0.0),
                                (other, 4, 0.0)])
    assert eng.free_pages < total
    assert eng.prefilling_slots == {s0, s1}
    assert eng.prefill_step() == []     # chunk 1: nobody finishes
    # cancel the neighbour mid-prefill: its pages must come back
    free_before = eng.free_pages
    eng.release(s1)
    assert s1 not in eng.prefilling_slots
    assert eng.free_pages > free_before
    out = {}
    while eng.prefilling_slots:
        for slot, tok in eng.prefill_step():
            out[slot] = [tok]
    got = out[s0]
    active = np.zeros((eng.max_slots,), np.bool_)
    active[s0] = True
    while len(got) < max_new:
        got.append(int(eng.step(active)[s0]))
    eng.release(s0)
    assert got == ref           # cancelled neighbour left no trace
    assert eng.free_pages == total
    assert eng.slots_active == 0


def test_chunked_prefill_batcher_exact_with_staggered_admission():
    """The interleaved scheduler (prompt chunks between decode
    iterations of resident slots) is invisible in the tokens."""
    from analytics_zoo_tpu.common import observability as obs
    eng = _engine(max_slots=2, prefill_chunk=4)
    rs = np.random.RandomState(9)
    jobs = [(rs.randint(1, VOCAB, size=n).tolist(), m)
            for n, m in [(11, 6), (14, 4), (3, 8), (9, 5), (7, 7)]]
    refs = [[int(t) for t in eng.generate(p, max_new_tokens=m)[0]]
            for p, m in jobs]
    cb = ContinuousBatcher(eng, queue_depth=16).start()
    try:
        futs = []
        for i, (p, m) in enumerate(jobs):
            futs.append(cb.submit(p, max_new_tokens=m))
            if i < 2:
                time.sleep(0.01)
        outs = [[int(t) for t in f.result(timeout=60)]
                for f in futs]
    finally:
        cb.stop()
    assert outs == refs
    assert eng.slots_active == 0
    assert eng.free_pages == eng.allocator.max_pages
    s = obs.snapshot()
    chunks = s["zoo_tpu_serving_gen_prefill_chunks_total"][
        "values"][0]["value"]
    assert chunks >= 3  # an 11-token prompt alone spans 3 chunks
    assert eng.stats()["prefill_chunk"] == 4


def test_speculative_greedy_engine_exact_with_rejections():
    """Greedy speculation is byte-identical to plain decode even when
    the drafter disagrees (rejection + corrected-token path)."""
    dnet, dparams = _toy_drafter()
    eng = _engine(spec_k=3, drafter=dnet, drafter_params=dparams)
    rs = np.random.RandomState(10)
    for plen, max_new in [(3, 9), (7, 6)]:
        prompt = rs.randint(1, VOCAB, size=plen).tolist()
        ref = [int(t) for t in
               eng.generate(prompt, max_new_tokens=max_new)[0]]
        (slot, first), = eng.admit([(prompt, max_new, 0.0)])
        got = _drive_to_completion(eng, slot, first, plen, max_new)
        eng.release(slot)
        assert got == ref, (prompt, got, ref)
    assert eng.spec_proposed > 0
    assert 0 <= eng.spec_accepted <= eng.spec_proposed
    st = eng.stats()
    assert st["spec_k"] == 3
    assert 0.0 <= st["spec_accept_rate"] <= 1.0


def test_speculative_self_draft_accepts_everything():
    """Drafter == target: every draft must be accepted and the bonus
    token appended — the full-accept cache-sync boundary (both caches
    advance k rows, no rewind) stays exact."""
    net, params = _toy_transformer()
    from analytics_zoo_tpu.pipeline.inference import (
        GenerationEngine)
    eng = GenerationEngine(net, params, max_slots=4,
                           max_context=SEQ, page_size=8, spec_k=2,
                           drafter=net, drafter_params=params)
    prompt, max_new = [4, 19, 7], 8
    ref = [int(t) for t in
           eng.generate(prompt, max_new_tokens=max_new)[0]]
    (slot, first), = eng.admit([(prompt, max_new, 0.0)])
    got = _drive_to_completion(eng, slot, first, len(prompt),
                               max_new)
    eng.release(slot)
    assert got == ref
    assert eng.spec_proposed > 0
    assert eng.spec_accepted == eng.spec_proposed


def test_speculative_batcher_greedy_exact_and_stats():
    from analytics_zoo_tpu.common import observability as obs
    dnet, dparams = _toy_drafter()
    eng = _engine(max_slots=2, spec_k=2, drafter=dnet,
                  drafter_params=dparams)
    rs = np.random.RandomState(12)
    jobs = [(rs.randint(1, VOCAB, size=n).tolist(), m)
            for n, m in [(3, 6), (7, 5), (2, 8), (5, 4)]]
    refs = [[int(t) for t in eng.generate(p, max_new_tokens=m)[0]]
            for p, m in jobs]
    cb = ContinuousBatcher(eng, queue_depth=16).start()
    try:
        futs = [cb.submit(p, max_new_tokens=m) for p, m in jobs]
        outs = [[int(t) for t in f.result(timeout=60)]
                for f in futs]
        st = cb.stats()
    finally:
        cb.stop()
    assert outs == refs
    assert st["spec_k"] == 2
    assert 0.0 <= st["spec_accept_rate"] <= 1.0
    assert eng.free_pages == eng.allocator.max_pages
    s = obs.snapshot()
    proposed = s["zoo_tpu_serving_gen_spec_proposed_total"][
        "values"][0]["value"]
    accepted = s["zoo_tpu_serving_gen_spec_accepted_total"][
        "values"][0]["value"]
    assert proposed > 0 and 0 <= accepted <= proposed


def test_speculative_sampled_smoke_and_eos():
    """Temperature > 0 speculation completes with the right budget
    and in-vocab tokens (distribution exactness is proven at the ops
    layer); eos raised mid-round stops the stream."""
    dnet, dparams = _toy_drafter()
    eng = _engine(max_slots=2, spec_k=3, drafter=dnet,
                  drafter_params=dparams)
    greedy = [int(t) for t in
              eng.generate([4, 19, 7], max_new_tokens=8)[0]]
    eos = greedy[2]
    k = greedy.index(eos)  # FIRST occurrence stops the stream
    cb = ContinuousBatcher(eng, queue_depth=8).start()
    try:
        sampled = cb.submit([9, 2, 31], max_new_tokens=10,
                            temperature=0.8).result(60)
        stopped = cb.submit([4, 19, 7], max_new_tokens=8,
                            eos_id=eos).result(60)
    finally:
        cb.stop()
    assert len(sampled) == 10
    assert all(0 <= int(t) < VOCAB for t in sampled)
    # greedy + eos: identical prefix, cut at eos inclusive — even
    # when the eos lands mid-speculative-round
    assert [int(t) for t in stopped] == greedy[:k + 1]


def test_speculative_accept_matches_target_distribution():
    """Rejection sampling is distribution-exact: over many k=1
    rounds with mismatched draft/target distributions, the emitted
    token's empirical law is the TARGET's, not a blend."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.sampling import speculative_accept
    rs = np.random.RandomState(6)
    v, n = 5, 20000
    p = rs.dirichlet(np.ones(v)).astype(np.float32)
    q = rs.dirichlet(np.ones(v)).astype(np.float32)
    kd, ka = jax.random.split(jax.random.key(0))
    drafts = jax.random.categorical(
        kd, jnp.log(jnp.broadcast_to(jnp.asarray(q), (n, v)))
    )[:, None].astype(jnp.int32)
    pb = jnp.broadcast_to(jnp.asarray(p), (n, 1, v))
    qb = jnp.broadcast_to(jnp.asarray(q), (n, 1, v))
    n_acc, corrected = speculative_accept(ka, pb, qb, drafts)
    emitted = np.where(np.asarray(n_acc) >= 1,
                       np.asarray(drafts)[:, 0],
                       np.asarray(corrected))
    hist = np.bincount(emitted, minlength=v) / n
    np.testing.assert_allclose(hist, p, atol=0.025)


@pytest.mark.parametrize("kv_dtype,atol", [("bf16", 2e-2),
                                           ("int8", 5e-2)])
def test_kv_dtype_conformance_matrix(kv_dtype, atol):
    """Reduced-precision KV storage: decode logits within the stated
    tolerance of the f32 cache (docs/serving.md), and this model's
    greedy argmax margins absorb it — identical token streams."""
    import jax.numpy as jnp
    net, params = _toy_transformer()
    prompt = [7, 3, 11, 2, 19, 33, 8]
    dt = {"bf16": jnp.bfloat16, "int8": jnp.int8}[kv_dtype]
    logits = {}
    for name, dtype in [("f32", jnp.float32), (kv_dtype, dt)]:
        cache = net.init_kv_cache(1, SEQ, page_size=8, dtype=dtype)
        ids = jnp.asarray([prompt], jnp.int32)
        pl = jnp.asarray([len(prompt)], jnp.int32)
        cache, lg = net.prefill(params, cache, ids, pl)
        tok, steps = int(jnp.argmax(lg[0])), []
        for _ in range(6):
            cache, lg = net.decode_step(
                params, cache, jnp.asarray([tok], jnp.int32),
                jnp.asarray([True]))
            steps.append(np.asarray(lg, np.float32))
            tok = int(jnp.argmax(lg[0]))
        logits[name] = np.concatenate(steps)
    np.testing.assert_allclose(logits[kv_dtype], logits["f32"],
                               atol=atol)
    assert np.argmax(logits[kv_dtype], -1).tolist() == \
        np.argmax(logits["f32"], -1).tolist()


def test_int8_engine_greedy_matches_f32_engine():
    eng8 = _engine(cache_dtype="int8")
    assert eng8.stats()["kv_dtype"] == "int8"
    assert eng8.cache.k_pages.dtype == np.int8
    assert eng8.cache.k_scales is not None
    engf = _engine()
    rs = np.random.RandomState(13)
    for plen, max_new in [(3, 6), (9, 5)]:
        prompt = rs.randint(1, VOCAB, size=plen).tolist()
        ref = [int(t) for t in
               engf.generate(prompt, max_new_tokens=max_new)[0]]
        (slot, first), = eng8.admit([(prompt, max_new, 0.0)])
        got = [first]
        active = np.zeros((eng8.max_slots,), np.bool_)
        active[slot] = True
        while len(got) < max_new:
            got.append(int(eng8.step(active)[slot]))
        eng8.release(slot)
        assert got == ref, (prompt, got, ref)


def test_no_steady_state_compiles_mixed_chunked_spec_traffic():
    """THE capacity-lever compile guarantee: chunked admissions,
    speculative rounds, regular tail steps and retirements across
    varied lengths/budgets/temperatures — zero compiles after
    warm()."""
    from jax import monitoring

    dnet, dparams = _toy_drafter()
    eng = _engine(prefill_chunk=4, spec_k=2, drafter=dnet,
                  drafter_params=dparams)
    rs = np.random.RandomState(14)
    compiles = []
    armed = [False]

    def listener(name, dur, **kw):
        if armed[0] and name.endswith("backend_compile_duration"):
            compiles.append(name)

    monitoring.register_event_duration_secs_listener(listener)
    cb = ContinuousBatcher(eng, queue_depth=32)
    try:
        cb.start()
        # step + chunk + draft + draft_chunk + verify, plus the
        # prefill buckets (both models) that single-chunk prompts
        # admit through
        assert eng.stats()["warmed_programs"] >= 5
        armed[0] = True
        mix = [(1, 3, 0.0), (11, 5, 0.0), (2, 4, 0.7), (17, 6, 0.0),
               (24, 2, 0.0), (5, 9, 0.9), (12, 1, 0.0), (7, 7, 0.0)]
        futs = []
        for n, m, temp in mix:
            futs.append(cb.submit(
                rs.randint(1, VOCAB, size=n).tolist(),
                max_new_tokens=m, temperature=temp))
            time.sleep(0.002)
        for f, (_, m, _) in zip(futs, mix):
            assert len(f.result(timeout=60)) == m
        armed[0] = False
        assert compiles == [], (
            f"chunked/speculative steady state compiled "
            f"{len(compiles)} times")
    finally:
        armed[0] = False
        cb.stop()
    assert eng.free_pages == eng.allocator.max_pages


def test_warm_compiles_excused_from_recompile_storm():
    """warm() AOT-compiles well past the storm threshold in one
    burst; the expected-compiles bracket keeps the anomaly quiet
    while still counting every compile."""
    from analytics_zoo_tpu.common import diagnostics
    from analytics_zoo_tpu.common import observability as obs
    dnet, dparams = _toy_drafter()
    eng = _engine(prefill_chunk=4, spec_k=2, drafter=dnet,
                  drafter_params=dparams)
    mon = diagnostics.RecompileMonitor(threshold=2, window_s=300.0)
    mon.install()
    before = mon.storms
    assert eng.warm() >= 5
    assert mon.storms == before, \
        "warm-up compiles fired a recompile_storm"
    s = obs.snapshot()
    assert s["zoo_tpu_xla_compiles_total"]["values"][0]["value"] > 0


def test_generate_route_over_http_sequential_path():
    import urllib.request
    im = _loaded_generator()
    ref = [int(t) for t in im.generate([2, 5], max_new_tokens=4)[0]]
    srv = InferenceServer(im, port=0, gen_batcher=None).start()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt": [2, 5],
                             "max_new_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
            assert json.loads(r.read())["tokens"] == ref
        health = json.loads(urllib.request.urlopen(
            url + "/health", timeout=30).read())
        gen = health["generator"]
        assert gen["enabled"] is False  # loaded, batcher not mounted
        assert gen["max_slots"] == 2
    finally:
        srv.stop()


# -- decode step: the page pools stay out of the layer scan -------------------

_KV_DTYPES = ["f32", "bf16", "int8"]


def _prefilled(kv, table=None, prompts=((7, 3, 11, 2, 19), (5, 9, 2))):
    """A two-slot cache with both prompts written, its net and params,
    and each slot's first sampled token."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.pipeline.inference.generation import \
        resolve_kv_dtype
    net, params = _toy_transformer()
    cache = net.init_kv_cache(len(prompts), SEQ, page_size=8,
                              dtype=resolve_kv_dtype(kv))
    if table is not None:
        cache = cache._replace(page_table=jnp.asarray(table, jnp.int32))
    width = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
    plens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    cache, logits = net.prefill(params, cache, jnp.asarray(ids), plens)
    return net, params, cache, jnp.argmax(logits, -1).astype(jnp.int32)


def _pools(cache):
    return [np.asarray(a) for a in (cache.k_pages, cache.v_pages,
                                    cache.k_scales, cache.v_scales)
            if a is not None]


def _layer_scan(jaxpr, length):
    """The scan over ``length`` layers, searched through nested
    jaxprs; (eqn, consts, xs, ys) with the scan's operands split."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and \
                eqn.params["length"] == length:
            nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
            return (eqn, eqn.invars[:nc], eqn.invars[nc + ncar:],
                    eqn.outvars[ncar:])
        for sub in eqn.params.values():
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                found = _layer_scan(inner, length)
                if found:
                    return found
    return None


@pytest.mark.parametrize("kv", ["f32", "int8"])
def test_decode_step_scans_no_page_pool(kv):
    """The pools are loop-invariant operands of the layer scan, never
    its scanned inputs or stacked outputs (as ``xs``/``ys`` XLA copied
    every layer's slab, and the whole stack, in every step)."""
    import jax
    net, params, cache, tok = _prefilled(kv)
    jaxpr = jax.make_jaxpr(net.decode_step)(
        params, cache, tok, np.array([True, True]))
    _, consts, xs, ys = _layer_scan(jaxpr.jaxpr, net.n_block)
    pooled = {a.shape for a in (cache.k_pages, cache.k_scales)
              if a is not None}
    slabs = {s[1:] for s in pooled}
    for v in list(xs) + list(ys):
        assert v.aval.shape not in pooled, v.aval
        assert v.aval.shape[1:] not in slabs, v.aval
    closed = [v.aval.shape for v in consts]
    n_pools = 2 * len(pooled)
    assert sum(s in pooled for s in closed) == n_pools, closed
    # what the scan stacks is one row a slot a layer
    rows = sorted(v.aval.shape for v in ys)
    assert len(rows) == n_pools
    assert all(s[:2] == (net.n_block, 2) and len(s) == 3 for s in rows)


def test_step_program_donates_the_pools():
    """`_step_fn` is compiled with the cache donated, and the program
    writes the pools in place: every cache leaf of the output aliases
    its input, and the buffers handed in are gone after the call."""
    import jax
    eng = _engine()
    (slot, _), = eng.admit([([4, 19, 7], 4, 0.0)])
    compiled = eng._get_step()
    n_leaves = len(jax.tree_util.tree_leaves(eng.cache))
    header = compiled.as_text().split("\n", 1)[0]
    if "input_output_alias" in header:     # the backend reports it
        aliased = header.split("input_output_alias={", 1)[1]
        for i in range(n_leaves):
            assert f"{{{i}}}: ({i}, " in aliased, (i, header)
    before = eng.cache
    active = np.zeros((eng.max_slots,), np.bool_)
    active[slot] = True
    eng.step(active)
    assert before.k_pages.is_deleted() and before.v_pages.is_deleted()
    assert not eng.cache.k_pages.is_deleted()


@pytest.mark.parametrize("kv", _KV_DTYPES)
def test_decode_step_freezes_inactive_slot(kv):
    """An inactive slot's pages and seq_lens are bit-identical after
    a step; the active neighbour gains exactly one row a layer."""
    net, params, cache, tok = _prefilled(kv)
    before = _pools(cache)
    after, _ = net.decode_step(params, cache, tok,
                               np.array([True, False]))
    assert np.asarray(after.seq_lens).tolist() == [6, 3]
    # identity table, 4 pages a slot: slot 1 owns pages 4..7
    for b, a in zip(before, _pools(after)):
        np.testing.assert_array_equal(a[:, 4:], b[:, 4:])
        changed = np.argwhere((a != b).reshape(a.shape[:3] + (-1,))
                              .any(-1))
        # slot 0's position 5 = page 0, offset 5, in every layer
        assert {tuple(c[1:]) for c in changed} == {(0, 5)}
        assert len(changed) == net.n_block


@pytest.mark.parametrize("kv", _KV_DTYPES)
def test_decode_step_full_context_writes_nothing(kv):
    """A slot already at ``max_context`` stays active but has no room:
    its row is dropped, never wrapped onto a live page."""
    import jax.numpy as jnp
    net, params, cache, tok = _prefilled(kv)
    cache = cache._replace(
        seq_lens=jnp.asarray([cache.max_context, 3], jnp.int32))
    before = _pools(cache)
    after, logits = net.decode_step(params, cache, tok,
                                    np.array([True, True]))
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    for b, a in zip(before, _pools(after)):
        np.testing.assert_array_equal(a[:, :4], b[:, :4])
        assert (a[:, 4:] != b[:, 4:]).any()     # slot 1 did append


@pytest.mark.parametrize("kv", _KV_DTYPES)
def test_appended_row_is_the_row_attention_saw(kv):
    """The view handed to attention (gather + the new row laid over
    it) equals a gather from the pools after the post-scan append."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import kv_cache as kvc
    net, params, cache, _ = _prefilled(kv)
    active = jnp.asarray([True, False])
    heads, hd = net.n_head, net.hidden_size // net.n_head
    views, rows = [], []
    for layer in range(net.n_block):
        k_new, v_new = jax.random.normal(
            jax.random.key(layer), (2, 2, heads, hd), jnp.float32)
        ctx, row = kvc.decode_view(cache, jnp.int32(layer), k_new,
                                   v_new, active=active)
        views.append(ctx)
        rows.append(row)
    stacked = tuple(None if r[0] is None else jnp.stack(r)
                    for r in zip(*rows))
    after = kvc.append_rows(cache, stacked, active=active)
    t = cache.max_context
    for layer, (k_ctx, v_ctx, k_sctx, v_sctx) in enumerate(views):
        assert k_ctx.shape == (2, t, heads, hd)
        assert k_ctx.dtype == cache.k_pages.dtype
        for seen, pool in ((k_ctx, after.k_pages),
                           (v_ctx, after.v_pages)):
            got = kvc.split_heads(kvc.gather_layer(
                pool, after.page_table, t, layer), heads, hd)
            np.testing.assert_array_equal(np.asarray(seen),
                                          np.asarray(got))
        for seen, pool in ((k_sctx, after.k_scales),
                           (v_sctx, after.v_scales)):
            assert (seen is None) == (pool is None)
            if seen is not None:
                np.testing.assert_array_equal(
                    np.asarray(seen), np.asarray(kvc.gather_layer(
                        pool, after.page_table, t, layer)))
    # the active slot's row is new, the frozen slot's view is the old
    assert (np.asarray(views[0][0][0, 5]) != 0).any()
    np.testing.assert_array_equal(
        np.asarray(views[0][0][1]),
        np.asarray(kvc.split_heads(kvc.gather_layer(
            cache.k_pages, cache.page_table, t, 0), heads, hd)[1]))


@pytest.mark.parametrize("kv", _KV_DTYPES)
def test_shuffled_page_table_same_tokens(kv):
    """Pages handed out by `PageAllocator` in shuffled order give the
    logits the identity table gives, bit for bit."""
    from analytics_zoo_tpu.ops.kv_cache import PageAllocator
    alloc = PageAllocator(8)
    pages = np.asarray(alloc.alloc(8))
    np.random.RandomState(3).shuffle(pages)
    table = pages.reshape(2, 4)
    assert table.tolist() != np.arange(8).reshape(2, 4).tolist()
    streams = []
    for tbl in (None, table):
        net, params, cache, tok = _prefilled(kv, table=tbl)
        out = []
        for _ in range(5):      # slot 0 walks onto its second page
            cache, logits = net.decode_step(params, cache, tok,
                                            np.array([True, True]))
            out.append(np.asarray(logits, np.float32))
            tok = logits.argmax(-1).astype(np.int32)
        streams.append(np.stack(out))
    np.testing.assert_array_equal(streams[0], streams[1])


# -- one decode step dispatched ahead ---------------------------------------
# The batcher dispatches step k before it fetches step k - 1: the
# slots' last tokens stay on the device and the mask is built from
# counts. Whatever it serves must be, token for token, what the same
# sequence of engine calls gives when every call is fetched at once
# (`admit`, `prefill_step`, plain `step`): each test logs the calls
# the loop makes and replays them on a twin engine.

def _log_engine_calls(eng):
    log = []

    def wrap(name):
        fn = getattr(eng, name)

        def logged(*args):
            log.append((name, tuple(
                a.copy() if isinstance(a, np.ndarray) else a
                for a in args)))
            return fn(*args)
        setattr(eng, name, logged)

    for name in ("admit_dispatch", "admit_partial",
                 "prefill_dispatch", "admit_from_handoff",
                 "dispatch", "release"):
        wrap(name)
    return log


def _replay_plain(twin, log):
    """The logged calls on ``twin``, each fetched before the next:
    every stream a slot's occupants produced, in admission order."""
    streams, at = [], {}
    for name, args in log:
        if name == "admit_dispatch":
            for slot, tok in twin.admit(args[0]):
                at[slot] = [tok]
                streams.append(at[slot])
        elif name == "admit_partial":
            for slot in twin.admit_partial(args[0]):
                at[slot] = []
                streams.append(at[slot])
        elif name == "prefill_dispatch":
            for slot, tok in twin.prefill_step():
                at[slot].append(tok)
        elif name == "admit_from_handoff":
            slot = twin.admit_from_handoff(*args)
            at[slot] = [int(args[0]["last_token"])]
            streams.append(at[slot])
        elif name == "dispatch":
            toks = twin.step(args[0])
            for slot in np.flatnonzero(args[0]):
                at[slot].append(int(toks[slot]))
        else:
            twin.release(args[0])
    return streams


def _counter(name):
    from analytics_zoo_tpu.common import observability as obs
    return obs.counter(name).value


def _serve_logged(jobs, temperature=0.0, eos_id=None, **kw):
    """``jobs`` of (prompt, max_new) through a started batcher, all
    queued before the loop starts (so the order of admission is the
    order of ``jobs``); the served streams beside the twin's replay
    of the same calls."""
    eng, twin = _engine(**kw), _engine(**kw)
    log = _log_engine_calls(eng)
    cb = ContinuousBatcher(eng, queue_depth=32)
    futs = [cb.submit(p, max_new_tokens=m, temperature=temperature,
                      eos_id=eos_id) for p, m in jobs]
    cb.start()
    try:
        outs = [[int(t) for t in f.result(timeout=120)]
                for f in futs]
    finally:
        cb.stop()
    assert eng.slots_active == 0
    assert eng.free_pages == eng.allocator.max_pages
    return outs, _replay_plain(twin, log), eng


_AHEAD_JOBS = [(3, 6), (7, 1), (2, 9), (5, 2), (4, 7), (6, 1), (9, 5)]


def _jobs(seed=4):
    rs = np.random.RandomState(seed)
    return [(rs.randint(1, VOCAB, size=n).tolist(), m)
            for n, m in _AHEAD_JOBS]


def test_ahead_greedy_staggered_matches_plain_steps():
    """Seven requests over two slots, budgets of 1, 2 and many:
    each answer is exactly its budget long, equals the twin's plain
    `step()` sequence and the whole-loop reference, and steps did
    run ahead of their fetches."""
    jobs = _jobs()
    ref_eng = _engine(max_slots=2)
    refs = [[int(t) for t in ref_eng.generate(p, max_new_tokens=m)[0]]
            for p, m in jobs]
    outs, plain, _ = _serve_logged(jobs, max_slots=2)
    assert [len(o) for o in outs] == [m for _, m in jobs]
    assert outs == plain == refs
    assert _counter("zoo_tpu_decode_steps_ahead_total") > 0
    assert _counter("zoo_tpu_decode_steps_ahead_total") < \
        _counter("zoo_tpu_serving_gen_steps_total")
    assert _counter("zoo_tpu_decode_rows_discarded_total") == 0


def test_ahead_sampled_fixed_seed_matches_plain_steps():
    """Temperature 0.9 under the engine's fixed seed: the step ids
    that seed sampling advance in the order of dispatch, so deferred
    fetches change no draw."""
    jobs = _jobs(seed=9)
    outs, plain, _ = _serve_logged(jobs, temperature=0.9,
                                   max_slots=2)
    assert [len(o) for o in outs] == [m for _, m in jobs]
    assert outs == plain
    greedy, _, _ = _serve_logged(jobs, max_slots=2)
    assert outs != greedy       # the temperature did sample


@pytest.mark.parametrize("max_new", [1, 2, 11])
def test_ahead_budget_met_by_count(max_new):
    """A lone request of budget 1 (its first token is its last: no
    step is dispatched for it), 2 and many."""
    prompt = [4, 19, 7]
    ref = [int(t) for t in _engine().generate(
        prompt, max_new_tokens=max_new)[0]]
    outs, plain, _ = _serve_logged([(prompt, max_new)])
    assert outs == plain == [ref]
    assert _counter("zoo_tpu_serving_gen_steps_total") == max_new - 1
    assert _counter("zoo_tpu_decode_steps_ahead_total") == \
        max(0, max_new - 2)


def test_ahead_eos_mid_stream_discards_the_row_in_flight():
    """A request that meets its `eos_id` while its next row is
    already running: the answer ends at the eos, the row is
    discarded and counted, and the neighbour's stream is whole."""
    other, ref_eng = [9, 2, 33, 5], _engine()
    rs = np.random.RandomState(2)
    for _ in range(40):     # a stream with a fresh token mid-way
        prompt = rs.randint(1, VOCAB, size=4).tolist()
        full = [int(t) for t in ref_eng.generate(
            prompt, max_new_tokens=12)[0]]
        k = next((i for i in range(2, 10)
                  if full[i] not in full[:i]), None)
        if k is not None:
            break
    assert k is not None
    eng, twin = _engine(max_slots=2), _engine(max_slots=2)
    log = _log_engine_calls(eng)
    cb = ContinuousBatcher(eng, queue_depth=8)
    f0 = cb.submit(prompt, max_new_tokens=12, eos_id=full[k])
    f1 = cb.submit(other, max_new_tokens=12)
    cb.start()
    try:
        out0 = [int(t) for t in f0.result(timeout=60)]
        out1 = [int(t) for t in f1.result(timeout=60)]
    finally:
        cb.stop()
    plain0, plain1 = _replay_plain(twin, log)
    assert out0 == full[:k + 1]          # eos included, nothing after
    assert plain0 == full[:k + 2]        # the row that ran ahead
    assert out1 == plain1 and len(out1) == 12
    assert _counter("zoo_tpu_decode_rows_discarded_total") == 1
    assert eng.slots_active == 0
    assert eng.free_pages == eng.allocator.max_pages


def test_ahead_chunked_admission_matches_plain_steps():
    """Prompts past one chunk are written a chunk a pass beside the
    running steps; the last chunk's first token reaches the step
    that consumes it without a fetch between."""
    rs = np.random.RandomState(11)
    jobs = [(rs.randint(1, VOCAB, size=n).tolist(), m)
            for n, m in [(3, 8), (14, 5), (5, 1), (11, 2), (2, 6)]]
    ref_eng = _engine(max_slots=2)
    refs = [[int(t) for t in ref_eng.generate(p, max_new_tokens=m)[0]]
            for p, m in jobs]
    outs, plain, eng = _serve_logged(jobs, max_slots=2,
                                     prefill_chunk=4)
    assert outs == refs
    # `admit_partial` lists a stream at its admission, `admit` at
    # its own: compare as sets of streams
    assert sorted(outs) == sorted(plain)
    assert _counter("zoo_tpu_serving_gen_prefill_chunks_total") == \
        4 + 2 + 3   # 14 tokens in chunks of 4, 5 and 11
    assert not eng.prefilling_slots


def test_ahead_answer_leaves_before_the_wait_for_a_chunk():
    """A request whose last token a step's fetch brings is answered
    before the pass waits for its chunk program (0.1-0.8 s on the
    chip at 2048 tokens): the future is done when that wait starts,
    not a pass later."""
    eng = _engine(max_slots=2, prefill_chunk=4)
    cb = ContinuousBatcher(eng, queue_depth=8)
    short = cb.submit([4, 19, 7], max_new_tokens=3)
    long_ = cb.submit(list(range(1, 15)), max_new_tokens=2)
    seen, collect = [], eng.collect

    def watched(h):
        # (a step's handle?, is the short request answered?)
        seen.append((h.width == eng.max_slots, short.done()))
        return collect(h)
    eng.collect = watched
    cb.start()
    try:
        assert len(short.result(timeout=60)) == 3
        assert len(long_.result(timeout=60)) == 2
    finally:
        cb.stop()
    first = next(i for i, (_, done) in enumerate(seen) if done)
    assert seen[first][0] is False          # a chunk's wait ...
    assert seen[first - 1] == (True, False)  # ... behind its step's


def test_ahead_handoff_import_matches_plain_steps():
    """A decode-pool engine takes the token the prefill side
    sampled, which the host alone knows, into the device's last
    tokens through the import program: the resumed stream equals
    the twin's plain steps and the monolithic reference."""
    prompt, max_new = [4, 19, 7, 3, 12], 7
    ref = [int(t) for t in _engine().generate(
        prompt, max_new_tokens=max_new)[0]]
    pre = _engine(role="prefill")
    (slot, first), = pre.admit([(prompt, max_new, 0.0)])
    blob = pre.export_handoff(slot)
    assert blob["last_token"] == first == ref[0]
    dec, twin = _engine(role="decode"), _engine(role="decode")
    log = _log_engine_calls(dec)
    cb = ContinuousBatcher(dec, queue_depth=4).start()
    try:
        out = [int(t) for t in cb.submit_handoff(
            blob, max_new_tokens=max_new).result(timeout=60)]
    finally:
        cb.stop()
    plain, = _replay_plain(twin, log)
    assert out == plain == ref
    assert dec.free_pages == dec.allocator.max_pages


def _resident(eng, n, timeout=20.0):
    """Wait until ``n`` slots are resident and two steps have been
    dispatched behind their prefills: from then on every dispatch
    finds the step before it unfetched."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if eng.slots_active == n and eng._step_id >= n + 2:
            return
        time.sleep(0.002)
    raise AssertionError("the loop never ran ahead")


def test_stop_with_a_step_in_flight_resolves_and_frees():
    from analytics_zoo_tpu.common import faults
    eng = _engine(max_slots=2)
    cb = ContinuousBatcher(eng, queue_depth=8,
                           max_new_cap=4096).start()
    try:
        faults.arm("generation/decode_step", "delay", seconds=0.05)
        futs = [cb.submit([4, 19, 7], max_new_tokens=25),
                cb.submit([9, 2], max_new_tokens=25)]
        _resident(eng, 2)
        queued = cb.submit([5], max_new_tokens=4)
        cb.stop(timeout=0.3)    # the drain cannot finish in time
    finally:
        faults.disarm_all()
        cb.stop()
    for f in futs:
        with pytest.raises(RuntimeError, match="stopped"):
            f.result(5)
    with pytest.raises(RuntimeError):
        queued.result(5)
    assert cb._flight is None
    assert eng.slots_active == 0
    assert eng.free_pages == eng.allocator.max_pages


def test_drain_with_a_step_in_flight_finishes_the_residents():
    from analytics_zoo_tpu.common import faults
    eng = _engine(max_slots=2)
    jobs = [([4, 19, 7], 20), ([9, 2], 17)]
    refs = [[int(t) for t in eng.generate(p, max_new_tokens=m)[0]]
            for p, m in jobs]
    cb = ContinuousBatcher(eng, queue_depth=8)
    futs = [cb.submit(p, max_new_tokens=m) for p, m in jobs]
    cb.start()
    try:
        faults.arm("generation/decode_step", "delay", seconds=0.03)
        _resident(eng, 2)
        assert cb.drain(timeout=30) is True
        assert [[int(t) for t in f.result(5)] for f in futs] == refs
        assert cb._flight is None
        assert eng.slots_active == 0
        assert eng.free_pages == eng.allocator.max_pages
    finally:
        faults.disarm_all()
        cb.stop()


def test_decode_fault_with_a_step_in_flight_fails_and_frees():
    """A `generation/decode_step` kill at the dispatch of step k,
    while step k - 1 is unfetched: every resident request fails,
    the handle is dropped, no slot or page stays claimed, and the
    loop serves the next request exactly."""
    from analytics_zoo_tpu.common import faults
    from analytics_zoo_tpu.common.faults import InjectedKillError
    eng = _engine(max_slots=2)
    ref = [int(t) for t in eng.generate([4, 19, 7],
                                        max_new_tokens=4)[0]]
    cb = ContinuousBatcher(eng, queue_depth=8).start()
    try:
        faults.arm("generation/decode_step", "delay", seconds=0.03)
        futs = [cb.submit([4, 19, 7], max_new_tokens=25),
                cb.submit([9, 2], max_new_tokens=25)]
        _resident(eng, 2)
        faults.arm("generation/decode_step", "kill", times=1)
        for f in futs:
            with pytest.raises(InjectedKillError):
                f.result(timeout=30)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and eng.slots_active:
            time.sleep(0.005)
        assert cb._flight is None
        assert eng.slots_active == 0
        assert eng.free_pages == eng.allocator.max_pages
        out = cb.submit([4, 19, 7], max_new_tokens=4).result(30)
        assert [int(t) for t in out] == ref
    finally:
        faults.disarm_all()
        cb.stop()


def test_step_chains_on_the_device_token_vector():
    """The compiled step takes the slots' last tokens as a device
    array and returns them, donated like the cache; two dispatches
    in a row need no host value of the first, and give what two
    fetched steps give."""
    import jax
    eng, twin = _engine(), _engine()
    reqs = [([4, 19, 7], 8, 0.0), ([9, 2], 8, 0.0)]
    h0 = eng.admit_dispatch(reqs)           # first tokens unfetched
    want0 = [t for _, t in twin.admit(reqs)]
    assert isinstance(eng._last_tok, jax.Array)
    active = np.zeros((eng.max_slots,), np.bool_)
    active[list(h0.slots)] = True
    before = eng._last_tok
    h1 = eng.dispatch(active)
    assert before.is_deleted()              # donated to the step
    h2 = eng.dispatch(active)
    assert not eng._last_tok.is_deleted()
    want1, want2 = twin.step(active), twin.step(active)
    # fetched late and out of order: the handles own their tokens
    got2, got1 = eng.collect(h2), eng.collect(h1)
    assert eng.collect(h0).tolist() == want0
    assert got1.tolist() == want1.tolist()
    assert got2.tolist() == want2.tolist()
    assert np.asarray(eng._last_tok)[active].tolist() == \
        want2[active].tolist()
    # an inactive slot keeps its entry
    assert np.asarray(eng._last_tok)[~active].tolist() == \
        np.asarray(twin._last_tok)[~active].tolist()


def test_only_the_step_program_is_named_step_fn():
    """`benchmark/readers/device.py::find_module` reads the step by
    the one traced program whose name holds `_step_fn`: of every
    program an engine can compile, chunked, speculating and on both
    sides of a handoff, exactly the step's does."""
    dnet, dparams = _toy_drafter()
    engines = [
        _engine(prefill_chunk=4, spec_k=2, drafter=dnet,
                drafter_params=dparams),
        _engine(role="prefill"), _engine(role="decode")]
    names = []
    for eng in engines:
        eng.warm()
        progs = [eng._compiled_step, eng._compiled_chunk,
                 eng._compiled_draft, eng._compiled_verify,
                 eng._compiled_draft_chunk,
                 eng._compiled_handoff_export,
                 eng._compiled_handoff_import,
                 *eng._compiled_prefill.values(),
                 *eng._compiled_draft_prefill.values()]
        mine = [p.as_text().split("\n", 1)[0].split()[1].rstrip(",")
                for p in progs if p is not None]
        assert sum("_step_fn" in n for n in mine) == \
            (eng.role != "prefill"), mine
        names += mine
    assert len(names) >= 10
