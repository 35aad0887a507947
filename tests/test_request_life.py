"""The request's life and the loop's slack, as `ContinuousBatcher`
records them (docs/observability.md, Span reference): one
`decode/first_token` a request on every admission path, the gaps
between a request's tokens and what they waited behind, and every
`decode/iteration` split into dispatch, wait and the host's own
time. Toy engines on the CPU. Tier-1 fast."""

import pytest

from analytics_zoo_tpu.common import observability as obs
from analytics_zoo_tpu.common import tracing

SEQ, VOCAB = 64, 61
CHUNK = 4
# the first request decodes for a while; the second is submitted at
# the first's third step, so it is admitted behind a resident decode
FIRST = (list(range(1, 15)), 40)
SECOND = (list(range(20, 29)), 5)
PATHS = ("prefill", "chunked", "speculative", "handoff_out")

TTFT = "zoo_tpu_serving_gen_ttft_seconds"
GAPS = "zoo_tpu_serving_gen_token_gap_seconds"
BEHIND = "zoo_tpu_serving_gen_token_gap_behind_prompt_seconds_total"
CHUNKS = "zoo_tpu_serving_gen_prefill_chunks_total"


def _net(n_block, hidden, key):
    import jax
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.api.keras.layers.transformer \
        import TransformerLayer
    init_nncontext(seed=0, log_level="WARNING")
    net = TransformerLayer(n_block=n_block, hidden_size=hidden,
                           n_head=2, seq_len=SEQ, vocab=VOCAB,
                           hidden_p_drop=0.0, attn_p_drop=0.0,
                           embed_p_drop=0.0)
    return net, net.build(jax.random.key(key), (SEQ,))


def _engine(path):
    from analytics_zoo_tpu.pipeline.inference.generation import \
        GenerationEngine
    kw = {}
    if path == "chunked":
        kw["prefill_chunk"] = CHUNK
    elif path == "speculative":
        kw["drafter"], kw["drafter_params"] = _net(1, 16, 7)
        kw["spec_k"] = 2
    elif path in ("handoff_out", "handoff_in"):
        kw["role"] = {"handoff_out": "prefill",
                      "handoff_in": "decode"}[path]
    net, params = _net(2, 32, 0)
    return GenerationEngine(net, params, max_slots=2,
                            max_context=SEQ, page_size=8, **kw)


def _metrics():
    """The counters and histograms the tests read, as plain numbers
    (the registry is cleared round every test)."""
    def hist(name):
        h = obs.histogram(name)
        return {"sum": h.sum, "count": h.count}
    return {TTFT: hist(TTFT), GAPS: hist(GAPS),
            "gaps_to_1ms": obs.histogram(GAPS).cumulative()[0][1],
            BEHIND: obs.counter(BEHIND).value,
            CHUNKS: obs.counter(CHUNKS).value}


def _run(path):
    """Two traced requests through a toy batcher on ``path``: their
    answers, every span record and the metrics."""
    from analytics_zoo_tpu.pipeline.inference.batching import \
        ContinuousBatcher
    eng = _engine(path)
    cb = ContinuousBatcher(eng, queue_depth=4)
    futs, ids, calls = [], [], [0]

    def submit(prompt, max_new):
        with tracing.trace("serving/request") as tr:
            ids.append(tr.trace_id)
            futs.append((cb.submit_prefill if path == "handoff_out"
                         else cb.submit)(prompt, max_new_tokens=max_new))

    def hooked(fn):
        def call(active):
            calls[0] += 1
            if calls[0] == 3:
                submit(*SECOND)
            return fn(active)
        return call
    eng.dispatch = hooked(eng.dispatch)
    eng.spec_step = hooked(eng.spec_step)
    cb.start()
    tracing.reset_tracing()
    try:
        submit(*FIRST)
        if path == "handoff_out":   # a prefill pool runs no step
            submit(*SECOND)
        while len(futs) < 2:
            futs[0].result(timeout=60)
        out = [f.result(timeout=60) for f in futs]
    finally:
        cb.stop()
    return {"path": path, "out": out, "metrics": _metrics(),
            "trace_ids": ids,
            "records": [r.to_dict()
                        for r in tracing.get_store().records()]}


@pytest.fixture(scope="module", params=PATHS)
def life(request):
    return _run(request.param)


def _named(run, name):
    """The records of one name; a request's in the order submitted."""
    recs = [r for r in run["records"] if r["name"] == name]
    order = run["trace_ids"]
    if any(r["trace_id"] not in order for r in recs):
        return recs
    return sorted(recs, key=lambda r: order.index(r["trace_id"]))


def test_first_token_record_on_every_admission_path(life):
    """One `decode/first_token` a request, from submit to the first
    hand-out: its duration is the value observed into the
    time-to-first-token histogram; the one-row path's equals
    `decode/admit`'s, the chunked path's is longer and counts the
    chunk programs that wrote the prompt."""
    path = life["path"]
    firsts = _named(life, "decode/first_token")
    admits = _named(life, "decode/admit")
    waits = _named(life, "decode/queue_wait")
    assert len(firsts) == len(admits) == len(waits) == 2
    ttft = life["metrics"][TTFT]
    assert ttft["count"] == 2
    # (a record's duration is kept to the microsecond)
    assert sum(r["dur_s"] for r in firsts) == pytest.approx(
        ttft["sum"], abs=2e-6)
    for r, a, w in zip(firsts, admits, waits):
        f = r["fields"]
        assert {"slot", "prompt_len", "path", "chunks",
                "queue_s"} <= set(f)
        assert r["trace_id"] == a["trace_id"] == w["trace_id"]
        assert f["slot"] == a["fields"]["slot"]
        assert f["queue_s"] == pytest.approx(w["dur_s"], abs=1e-6)
        assert f["queue_s"] <= r["dur_s"]
        if path == "chunked":
            assert f["path"] == "chunked"
            assert f["chunks"] == -(-f["prompt_len"] // CHUNK)
            # `decode/admit` ends where the slot was claimed
            assert r["dur_s"] > a["dur_s"]
        else:
            assert f["path"] == ("handoff_out" if path == "handoff_out"
                                 else "prefill")
            assert f["chunks"] == 0
            assert r["dur_s"] == a["dur_s"]
    assert sum(r["fields"]["chunks"] for r in firsts) == \
        life["metrics"][CHUNKS]
    assert [r["fields"]["prompt_len"] for r in firsts] == [14, 9]


def test_gaps_sum_to_the_life_after_the_first_token(life):
    """Every token after the first passes one gap computation:
    `decode/retire` carries their mean and longest beside the time
    to first token, and they sum to what is left of the request's
    life; the histogram holds one observation a gap."""
    retires = _named(life, "decode/retire")
    gaps = life["metrics"][GAPS]
    if life["path"] == "handoff_out":
        # the answer is the blob: nothing decodes here
        assert not retires and gaps["count"] == 0
        assert len(_named(life, "decode/handoff_export")) == 2
        return
    firsts = {r["trace_id"]: r for r in
              _named(life, "decode/first_token")}
    assert len(retires) == 2
    total = 0.0
    for r, out in zip(retires, life["out"]):
        f = r["fields"]
        assert f["tokens"] == len(out)
        first = firsts[r["trace_id"]]
        assert f["first_token_s"] == pytest.approx(first["dur_s"],
                                                   abs=1e-6)
        n = f["tokens"] - 1
        assert f["gap_mean_s"] * n == pytest.approx(
            r["dur_s"] - first["dur_s"], abs=1e-6 * (n + 2))
        assert f["gap_max_s"] >= f["gap_mean_s"] > 0
        assert 0 <= f["gaps_behind_prompt"] <= n
        total += r["dur_s"] - first["dur_s"]
    assert gaps["count"] == sum(len(o) - 1 for o in life["out"])
    assert gaps["sum"] == pytest.approx(total, abs=1e-5)


def test_gaps_behind_a_prompt_are_counted(life):
    """The second request's prompt programs run before steps of the
    first: the gaps those steps' tokens close are counted, on the
    request and in the counter, which never passes the histogram's
    sum; a request's own first gap never counts."""
    if life["path"] == "handoff_out":
        assert life["metrics"][BEHIND] == 0
        return
    first, second = _named(life, "decode/retire")
    behind = life["metrics"][BEHIND]
    assert 0 < behind <= life["metrics"][GAPS]["sum"]
    want = 3 if life["path"] == "chunked" else 1    # programs of 9
    assert first["fields"]["gaps_behind_prompt"] == want
    assert second["fields"]["gaps_behind_prompt"] == 0
    assert behind <= want * first["fields"]["gap_max_s"] + 1e-6


def test_iteration_splits_into_dispatch_wait_and_host(life):
    its = _named(life, "decode/iteration")
    assert its
    for r in its:
        f = r["fields"]
        assert {"admitted", "active", "emitted", "retired",
                "dispatch_s", "wait_s", "programs"} <= set(f)
        assert f["dispatch_s"] >= 0 and f["wait_s"] >= 0
        assert f["dispatch_s"] + f["wait_s"] <= r["dur_s"] + 1e-4
        assert (f["programs"] > 0) == (f["dispatch_s"] > 0)
    programs = sum(r["fields"]["programs"] for r in its)
    steps = len(_named(life, "decode/step"))
    rounds = len(_named(life, "decode/spec_step"))
    chunks = int(life["metrics"][CHUNKS])
    prefills = sum(r["fields"]["calls"]
                   for r in _named(life, "decode/prefill"))
    assert programs == steps + 2 * rounds + chunks + prefills
    # a pass that fetched waited: the step before, or a first token
    assert sum(r["fields"]["wait_s"] for r in its) > 0
    # no span is left round `engine.release`
    assert not _named(life, "decode/release")
    assert obs.snapshot().get("zoo_tpu_decode_release_seconds") is None


def test_trace_off_no_record_and_the_same_tokens(monkeypatch, life):
    on, path = life, life["path"]
    monkeypatch.setenv("ZOO_TPU_TRACE", "0")
    off = _run(path)
    assert off["records"] == []
    if path == "handoff_out":
        assert [b["last_token"] for b in off["out"]] == \
            [b["last_token"] for b in on["out"]]
    else:
        assert [o.tolist() for o in off["out"]] == \
            [o.tolist() for o in on["out"]]
    # the histograms and the counter are metrics, not records
    assert off["metrics"][TTFT]["count"] == 2
    assert off["metrics"][GAPS]["count"] == on["metrics"][GAPS]["count"]


def test_a_speculative_rounds_tokens_leave_at_one_instant(life):
    """Tokens of one round are handed out together: all but the
    round's first close gaps of 0, and are counted as such; on the
    other paths every gap is a step's."""
    run = life
    if run["path"] != "speculative":
        assert not _named(run, "decode/spec_step")
        assert run["metrics"][GAPS]["count"] == sum(
            r["fields"]["emitted"]
            for r in _named(run, "decode/iteration"))
        return
    rounds = _named(run, "decode/spec_step")
    tokens = sum(len(o) for o in run["out"])
    assert rounds and run["metrics"][GAPS]["count"] == tokens - 2
    # a round closes one gap of its own length a slot; so does a
    # plain step, which a slot near its budget takes instead
    timed = sum(r["fields"]["n"] for r in
                rounds + _named(run, "decode/step"))
    assert timed < tokens - 2
    assert run["metrics"]["gaps_to_1ms"] >= tokens - 2 - timed


def test_decode_side_handoff_gets_no_first_token_record():
    """Its first token was sampled on the prefill side: no
    `decode/first_token`, `first_token_s` 0, and its first gap runs
    from the splice."""
    from analytics_zoo_tpu.pipeline.inference.batching import \
        ContinuousBatcher
    pre = _engine("handoff_out")
    (slot, _first), = pre.admit([(FIRST[0], 6, 0.0)])
    blob = pre.export_handoff(slot)
    cb = ContinuousBatcher(_engine("handoff_in"), queue_depth=4)
    cb.start()
    tracing.reset_tracing()
    try:
        with tracing.trace("serving/request"):
            fut = cb.submit_handoff(blob, max_new_tokens=6)
        out = fut.result(timeout=60)
    finally:
        cb.stop()
    names = [r.name for r in tracing.get_store().records()]
    assert "decode/first_token" not in names
    retire, = [r for r in tracing.get_store().records()
               if r.name == "decode/retire"]
    assert len(out) == retire.fields["tokens"] == 6
    assert retire.fields["first_token_s"] == 0.0
    assert retire.fields["gap_max_s"] >= retire.fields["gap_mean_s"] > 0
    assert obs.histogram(GAPS).count == 5
    assert obs.histogram(TTFT).count == 0


# -- scripts/traced_cell.py: the builder's traced run, committed ------------

def _traced_cell():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "traced_cell.py")
    spec = importlib.util.spec_from_file_location("traced_cell", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_traced_cell_sums_the_windows_spans_by_name():
    tc = _traced_cell()
    spans = [{"name": "decode/iteration", "t_start": 10.0 + 0.01 * i,
              "dur_s": 0.008, "trace_id": "t",
              "fields": {"wait_s": 0.001 * i, "programs": 2,
                         "path": "prefill", "ok": True}}
             for i in range(5)]
    spans.append({"name": "decode/retire", "t_start": 1.0,
                  "dur_s": 0.5, "trace_id": "r", "fields": {}})
    got = tc.spans_by_name(spans)
    it = got["decode/iteration"]
    assert it["count"] == 5
    assert it["dur_s"] == {"mean": pytest.approx(0.008),
                           "p50": 0.008, "p95": 0.008}
    assert it["start_gap_s"]["p50"] == pytest.approx(0.01)
    # numeric fields only: no string, no flag
    assert set(it["fields"]) == {"wait_s", "programs"}
    assert it["fields"]["wait_s"] == {
        "mean": pytest.approx(0.002), "p50": 0.002, "p95": 0.004}
    assert got["decode/retire"] == {
        "count": 1, "dur_s": {"mean": 0.5, "p50": 0.5, "p95": 0.5},
        "fields": {}}


def test_traced_cell_reads_metrics_no_cell_lists():
    """The harness's own reader loop over metric files the cell does
    not list; one that finds nothing to read is left out."""
    tc = _traced_cell()
    layers = {"spans": [{"name": "decode/iteration", "t_start": 1.0,
                         "dur_s": 0.010, "trace_id": "t",
                         "fields": {"wait_s": 0.0075}}],
              "counters": {}}
    got = tc.extra_metrics(["pass_host_ms.generate",
                            "loop_wait_pct.generate",
                            "steps_ahead_pct.generate"], layers)
    assert got == {
        "pass_host_ms.generate": {"value": pytest.approx(2.5),
                                  "unit": "ms"},
        "loop_wait_pct.generate": {"value": pytest.approx(75.0),
                                   "unit": "%"}}
    assert tc.main(["gpt2xl-generate-chat8"]) == 2   # usage
