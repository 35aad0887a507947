"""Generation serving engine: device state + compiled programs for
autoregressive decode.

The model layer owns the math (`TransformerLayer.prefill` /
`decode_step` / `generate` — `pipeline/api/keras/layers/transformer.py`);
this module owns everything a *server* needs around it:

- ONE resident :class:`~analytics_zoo_tpu.ops.kv_cache.PagedKVCache`
  sized ``(max_slots, max_context)``, with the host-side
  `PageAllocator` assigning physical pages to slots at admission and
  reclaiming them at retirement — the vLLM bookkeeping half;
- ONE compiled decode-step program (shape-static over the full slot
  array, inactive slots frozen by the ``active`` mask; it takes and
  returns the slots' last tokens as a device array, so step k + 1 is
  dispatched with no host value of step k: :meth:`dispatch` returns
  a handle, :meth:`collect` fetches its tokens) plus one
  compiled prefill program per prompt-length bucket (powers of two
  from ``PROMPT_BUCKET_FLOOR`` up), each of ONE prompt row addressed
  to its slot: an admission runs the prompts it admits and no other
  slot — after :meth:`GenerationEngine.warm`, steady-state
  serving performs **zero** compilations regardless of the
  prompt/output-length mix;
- per-slot sampling state: a traced ``(max_slots,)`` temperature
  vector (per-request temperature without recompiles) and a static
  ``top_k`` (``ZOO_TPU_GEN_TOP_K``);
- a sequential whole-loop :meth:`generate` (the model's compiled
  `lax.while_loop` path, jit-cached per shape) — the per-request
  baseline `InferenceModel.generate` serves and `bench_generate.py`
  A/Bs continuous batching against.

Three capacity levers layer on top (each off by default, all
compounding — docs/serving.md has the tuning guide):

- **Chunked prefill** (``ZOO_TPU_PREFILL_CHUNK`` = chunk width C,
  0 = off): :meth:`admit_partial` assigns slots/pages WITHOUT running
  the prompt; :meth:`prefill_step` then advances ONE prefilling
  slot, the one whose turn it is, by at most C prompt tokens through
  ONE compiled chunk program (the net's ``forward_chunk``) of ONE
  row addressed to that slot, so the batcher interleaves exactly one
  bounded chunk with every decode iteration however many prompts are
  in flight — resident sequences never wait for more than one
  chunk's latency between tokens, and a short prompt admitted behind
  a long one takes turns with it instead of waiting it out.
- **Int8 paged KV** (``ZOO_TPU_KV_DTYPE=int8|bf16|f32``): the cache
  pools quantize per row with per-page scale arrays
  (`ops/kv_cache.quantize_rows`) — ~2x resident sequences per chip
  for a bounded accuracy cost (the kv-dtype conformance matrix in
  tests/test_generate.py states the tolerance).
- **Speculative decoding** (``ZOO_TPU_SPEC_K`` = draft length k,
  0 = off; needs a ``drafter`` net registered through
  `InferenceModel.load_generator`): a small drafter proposes k
  tokens (one compiled scan, :meth:`_get_draft`), the target scores
  all k in ONE verify chunk (`forward_chunk(all_logits=True)`), and
  rejection sampling (`ops/sampling.speculative_accept`) accepts a
  prefix — distribution-exact for temperature sampling, byte-exact
  for greedy. Both caches simply rewind ``seq_lens`` on rejection
  (stale rows past the length are invisible by construction), and
  the drafter's pages mirror the target's table, so page accounting
  is unchanged.

The engine is NOT thread-safe by design: exactly one driver — the
:class:`~analytics_zoo_tpu.pipeline.inference.batching.ContinuousBatcher`
loop thread, or a caller of :meth:`generate` — may touch it at a time
(the batcher serializes admission, stepping, and retirement by
construction, the same single-dispatcher discipline DynamicBatcher
uses).

Configuration (constructor kwargs override the environment):
``ZOO_TPU_GEN_SLOTS`` (default 8), ``ZOO_TPU_GEN_MAX_CONTEXT``
(default: the net's ``seq_len``), ``ZOO_TPU_GEN_PAGE_SIZE`` (16),
``ZOO_TPU_GEN_TOP_K`` (0 = full softmax), ``ZOO_TPU_KV_DTYPE``
(f32), ``ZOO_TPU_PREFILL_CHUNK`` (0 = whole-prompt prefill),
``ZOO_TPU_SPEC_K`` (0 = no speculation). docs/serving.md has the
slot/page sizing guide, docs/perf_flags.md the flag catalog.

Every AOT compile here is *deliberate* (warm-up or first-use of a
known program), so they are bracketed with
`diagnostics.expected_compiles()` — the RecompileMonitor keeps its
total count but excludes them from the storm window (a warm() of
step + buckets used to fire a spurious ``recompile_storm``).
"""

from __future__ import annotations

import os
import time
from typing import Optional, Sequence

import numpy as np

from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.common import observability as obs
from analytics_zoo_tpu.pipeline.inference.batching import bucket_ladder

__all__ = ["Dispatched", "GenerationEngine", "resolve_kv_dtype"]

# chaos hook: armed via ZOO_TPU_FAULTS or tests (docs/robustness.md);
# a "kill" here simulates the device/replica dying mid-decode with
# resident sequences holding KV pages
_STEP_FAULT = faults.point("generation/decode_step")

_KV_DTYPES = ("f32", "bf16", "int8")

# The shortest prompt bucket. A prefill program holds ONE prompt, so
# below a few dozen tokens its time is the read of the weights
# whatever the bucket (a v5e's ridge is 240 FLOPs a byte: about 240
# tokens over bf16 weights), and shorter buckets would be programs to
# compile, cache and warm that buy nothing: on a v5e the one-row
# programs of 8 / 16 / 32 / 64 / 128 tokens take 7.5 / 7.8 / 8.0 /
# 8.9 / 10.1 ms at GPT-2-XL's widths and 9.7 / 10.5 / 13.8 / 17.3 /
# 22.3 ms at DeepSeek-V2's (PERF.md, PR 33). 32 and not more, because
# an expert model's programs grow from the first token on (more
# tokens reach more experts).
PROMPT_BUCKET_FLOOR = 32


def prompt_ladder(longest: int) -> "tuple[int, ...]":
    """The padded prompt lengths an engine compiles for prompts of
    up to ``longest`` tokens: powers of two from
    ``PROMPT_BUCKET_FLOOR`` up, ``longest`` itself the last."""
    return tuple(b for b in bucket_ladder(longest)
                 if b >= min(PROMPT_BUCKET_FLOOR, longest))


def resolve_kv_dtype(cache_dtype=None):
    """Resolve the paged-cache storage dtype: an explicit dtype (or
    its string name) wins, else ``ZOO_TPU_KV_DTYPE`` (default f32 —
    bit-identical to PR 8; bf16 halves cache HBM, int8 halves it
    again with per-page scales). Returns a jnp dtype."""
    import jax.numpy as jnp
    named = {"f32": jnp.float32, "float32": jnp.float32,
             "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
             "int8": jnp.int8}
    if cache_dtype is None:
        cache_dtype = os.environ.get("ZOO_TPU_KV_DTYPE", "f32")
    if isinstance(cache_dtype, str):
        if cache_dtype not in named:
            raise ValueError(
                f"ZOO_TPU_KV_DTYPE {cache_dtype!r} not one of "
                f"{_KV_DTYPES}")
        return named[cache_dtype]
    return cache_dtype


class Dispatched:
    """What a dispatched call left on the device for the host to
    fetch (:meth:`GenerationEngine.collect`): in ``outs`` one token
    vector a program — a step's over every slot, a prefill's or a
    chunk's of its one row — with the program's counts behind its
    first ``width`` entries. ``slots``: whose first tokens a
    prefill's or a prompt's last chunk's are, in the order of
    ``outs`` (empty for a step, and for a chunk with prompt left).
    ``dispatch_s``: the compiled calls returning (a step's one, an
    admission's one a request, a chunk's); ``fetch_s``: the wait for
    the tokens, once collected."""

    __slots__ = ("outs", "width", "slots", "dispatch_s", "fetch_s")

    def __init__(self, outs, width, slots=(), dispatch_s=0.0):
        self.outs, self.width, self.slots = outs, width, slots
        self.dispatch_s, self.fetch_s = dispatch_s, 0.0


class GenerationEngine:
    """Resident decode state + compiled programs for one generative
    net (module docstring has the design).

    ``net`` must expose the decode surface the transformer layer
    defines: ``init_kv_cache / prefill / decode_step / forward_chunk
    / generate`` and ``seq_len`` (the most positions it takes) /
    ``vocab`` attributes (duck-typed — any net with those methods
    serves: `TransformerLayer` with its K/V pools, `PatternDecoder`
    with its row pools). A net without ``forward_chunk`` serves
    whole-prompt prefill only: ``prefill_chunk > 0`` and
    ``spec_k > 0`` are refused for it here. A net that names
    ``step_counters`` has ``decode_step(..., stats=True)``,
    ``prefill(..., stats=True)`` and
    ``forward_chunk(..., stats=True)`` return their counts, which
    come back in the tokens' fetch and go to its
    ``record_step_counts``. A ``drafter`` (same
    surface, same vocab, typically far fewer blocks) plus
    ``spec_k > 0`` turns on speculative decoding.
    """

    def __init__(self, net, params, *,
                 max_slots: Optional[int] = None,
                 max_context: Optional[int] = None,
                 page_size: Optional[int] = None,
                 top_k: Optional[int] = None,
                 cache_dtype=None,
                 prefill_chunk: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 drafter=None, drafter_params=None,
                 rng_seed: int = 0,
                 role: str = "both"):
        import jax

        env = os.environ
        if max_slots is None:
            max_slots = int(env.get("ZOO_TPU_GEN_SLOTS", 8))
        if max_context is None:
            max_context = int(env.get("ZOO_TPU_GEN_MAX_CONTEXT",
                                      net.seq_len))
        if page_size is None:
            page_size = int(env.get("ZOO_TPU_GEN_PAGE_SIZE", 16))
        if top_k is None:
            top_k = int(env.get("ZOO_TPU_GEN_TOP_K", 0))
        if prefill_chunk is None:
            prefill_chunk = int(env.get("ZOO_TPU_PREFILL_CHUNK", 0))
        if spec_k is None:
            spec_k = int(env.get("ZOO_TPU_SPEC_K", 0))
        if max_context > net.seq_len:
            raise ValueError(
                f"max_context {max_context} exceeds the positions "
                f"the net takes (seq_len {net.seq_len})")
        if not hasattr(net, "forward_chunk") and (
                int(prefill_chunk) > 0 or int(spec_k) > 0):
            raise ValueError(
                f"{type(net).__name__} has no forward_chunk: chunked "
                "prefill (prefill_chunk > 0) and speculative verify "
                "(spec_k > 0) are not available for it; use 0")
        self.net = net
        self.params = params
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        self.top_k = int(top_k)
        self.cache_dtype = resolve_kv_dtype(cache_dtype)
        self.prefill_chunk = max(0, int(prefill_chunk))
        self.spec_k = max(0, int(spec_k))
        self.drafter = drafter
        self.drafter_params = drafter_params
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role {role!r} not one of 'prefill'/'decode'/'both'")
        self.role = role
        if self.spec_k > 0 and drafter is None:
            raise ValueError(
                "spec_k > 0 needs a drafter net (load_generator"
                "(..., drafter=..., drafter_params=...))")
        if self.spec_k > 0 and role != "both":
            # the drafter's cache state cannot be reconstructed from
            # a handoff blob without re-running its forward pass, so
            # speculation stays a monolithic-engine lever
            raise ValueError(
                "speculative decoding (spec_k > 0) is incompatible "
                "with disaggregated roles; use role='both'")
        if self.spec_k > 1_000:
            raise ValueError(f"spec_k {self.spec_k} is absurd")

        from analytics_zoo_tpu.ops import kv_cache as kvc
        # the most tokens one forward_chunk call writes a slot (a
        # prompt chunk, or a speculative round's k + 1)
        max_chunk = max(1, self.prefill_chunk,
                        self.spec_k + 1 if self.spec_k else 0)
        cache = net.init_kv_cache(self.max_slots, int(max_context),
                                  page_size=self.page_size,
                                  dtype=self.cache_dtype,
                                  max_chunk=max_chunk)
        self.max_context = cache.max_context  # whole-page rounded
        self.pages_per_slot = cache.page_table.shape[1]
        # the engine owns page placement: blank the identity table and
        # hand every physical page to the allocator
        self._table = np.zeros(
            (self.max_slots, self.pages_per_slot), np.int32)
        self.cache = cache._replace(
            page_table=jax.numpy.asarray(self._table))
        self.allocator = kvc.PageAllocator(cache.num_pages)
        if role != "both":
            kvc.refuse_row_handoff(cache)
        self._slot_pages: "dict[int, list]" = {}
        self.free_slots = set(range(self.max_slots))

        # drafter state: its own (smaller) page pool, but the SAME
        # slot/page geometry and the SAME table — the target's page
        # accounting covers both, and seq_lens stay in lockstep
        # because draft/verify rewind them together
        self._draft_cache = None
        if drafter is not None and self.spec_k > 0:
            if int(drafter.vocab) != int(net.vocab):
                raise ValueError(
                    f"drafter vocab {drafter.vocab} != target vocab "
                    f"{net.vocab}")
            if self.max_context > drafter.seq_len:
                raise ValueError(
                    f"max_context {self.max_context} exceeds the "
                    f"drafter's position table ({drafter.seq_len})")
            dcache = drafter.init_kv_cache(
                self.max_slots, int(max_context),
                page_size=self.page_size, dtype=self.cache_dtype,
                max_chunk=max_chunk)
            # own device copy of the table — the compiled programs
            # donate whole cache pytrees, and a buffer shared with
            # the target cache would be deleted out from under it
            self._draft_cache = dcache._replace(
                page_table=jax.numpy.array(self._table))

        # per-slot sampling state (traced per call — no recompiles)
        self._temps = np.zeros((self.max_slots,), np.float32)
        # each slot's last sampled token, ON THE DEVICE: the step
        # program takes and returns it, and the programs that bring
        # a slot in (prefill, a prompt's last chunk, handoff import)
        # write its entry, so no step waits for a host value
        self._last_tok = jax.numpy.zeros((self.max_slots,),
                                         jax.numpy.int32)
        self._rng = jax.random.key(int(rng_seed))
        self._step_id = 0

        # chunked-prefill scheduler state: slot -> [ids, next_offset]
        # (prompts admitted but not yet fully written to the cache)
        self._pending_prompts: "dict[int, list]" = {}
        # (slot, start, tokens) of the latest `prefill_step`'s chunk
        # (`decode/prefill_chunk` fields)
        self.chunk_work: "tuple | None" = None

        # speculative acceptance accounting (bench + /health)
        self.spec_proposed = 0
        self.spec_accepted = 0
        # (dispatch_s, fetch_s) of the latest `spec_step`: its two
        # compiled calls returning, then the wait for what they hold
        self.spec_times = (0.0, 0.0)

        # prompt-length buckets: powers of two from the floor up,
        # capped at what the position table and the cache can hold
        self.prompt_buckets = prompt_ladder(
            min(self.max_context, int(net.seq_len)))

        # (calls, rows) of the latest `admit`: prefill programs run,
        # and the prompt rows they held (`decode/prefill` fields)
        self.prefill_counts = (0, 0)
        self._compiled_step = None
        self._compiled_prefill: dict = {}
        self._compiled_chunk = None
        self._compiled_draft_prefill: dict = {}
        self._compiled_draft_chunk = None
        self._compiled_draft = None
        self._compiled_verify = None
        self._compiled_handoff_export = None
        self._compiled_handoff_import = None
        self._gen_jits: dict = {}

    # -- compiled programs --------------------------------------------------
    def _step_fn(self, cache, params, tok, active, temps, rng, step):
        import jax
        from analytics_zoo_tpu.ops.sampling import sample_tokens
        counted = bool(getattr(self.net, "step_counters", ()))
        cache, logits, *counts = self.net.decode_step(
            params, cache, tok, active=active,
            **({"stats": True} if counted else {}))
        nxt = sample_tokens(jax.random.fold_in(rng, step),
                            logits.astype(jax.numpy.float32), temps,
                            self.top_k)
        # the last tokens stay on the device for the next step; the
        # step's counts ride behind the fetched tokens: one fetch
        return cache, jax.numpy.where(active, nxt, tok), \
            jax.numpy.concatenate([nxt] + counts) if counts else nxt

    def _prefill_fn(self, cache, params, last, ids, plens, slots,
                    temps, rng, step):
        import jax
        from analytics_zoo_tpu.ops.sampling import sample_tokens
        counted = bool(getattr(self.net, "step_counters", ()))
        cache, logits, *counts = self.net.prefill(
            params, cache, ids, plens, slots,
            **({"stats": True} if counted else {}))
        nxt = sample_tokens(jax.random.fold_in(rng, step),
                            logits.astype(jax.numpy.float32), temps,
                            self.top_k)
        # the first token goes to its slot's entry on the device;
        # as a chunk's, the prompt's counts ride behind the fetched one
        return cache, last.at[slots].set(nxt), \
            jax.numpy.concatenate([nxt] + counts) if counts else nxt

    def _abstract(self, tree):
        import jax
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                           np.asarray(a).dtype)
            if not hasattr(a, "aval") else
            jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def _chunk_fn(self, cache, params, last, ids, starts, n_new,
                  slots, temps, rng, step):
        import jax
        from analytics_zoo_tpu.ops.sampling import sample_tokens
        counted = bool(getattr(self.net, "step_counters", ()))
        cache, logits, *counts = self.net.forward_chunk(
            params, cache, ids, starts, n_new, slots=slots,
            **({"stats": True} if counted else {}))
        nxt = sample_tokens(jax.random.fold_in(rng, step),
                            logits.astype(jax.numpy.float32), temps,
                            self.top_k)
        # every chunk writes its slot's entry: the slot decodes only
        # after its prompt's last chunk, whose token then stands there
        return cache, last.at[slots].set(nxt), \
            jax.numpy.concatenate([nxt] + counts) if counts else nxt

    def _draft_prefill_fn(self, dcache, dparams, ids, plens, slots):
        dcache, _ = self.drafter.prefill(dparams, dcache, ids, plens,
                                         slots)
        return dcache

    def _draft_chunk_fn(self, dcache, dparams, ids, starts, n_new,
                        slots):
        dcache, _ = self.drafter.forward_chunk(
            dparams, dcache, ids, starts, n_new, slots=slots)
        return dcache

    def _draft_fn(self, dcache, dparams, t0, active, temps, rng,
                  step):
        """Propose ``spec_k`` draft tokens per active slot: a scan of
        drafter decode steps, each sampling with the slot's OWN
        temperature/top_k so the proposal distribution q (returned
        per step, (S, K, V)) is exactly what `speculative_accept`
        needs. Consumes [t0, d1, …, d_{k-1}]; proposes [d1, …, dk]."""
        import jax
        from analytics_zoo_tpu.ops.sampling import (sample_tokens,
                                                    sampling_probs)
        base = jax.random.fold_in(rng, step)

        def body(carry, i):
            dcache, tok = carry
            dcache, logits = self.drafter.decode_step(
                dparams, dcache, tok, active=active)
            logits = logits.astype(jax.numpy.float32)
            nxt = sample_tokens(jax.random.fold_in(base, i), logits,
                                temps, self.top_k)
            q = sampling_probs(logits, temps, self.top_k)
            return (dcache, nxt), (nxt, q)

        (dcache, _), (drafts, qs) = jax.lax.scan(
            body, (dcache, t0),
            jax.numpy.arange(self.spec_k, dtype=jax.numpy.int32))
        return (dcache, jax.numpy.transpose(drafts, (1, 0)),
                jax.numpy.transpose(qs, (1, 0, 2)))

    def _verify_fn(self, cache, dcache, params, t0, drafts, qprobs,
                   active, temps, rng, step):
        """One compiled speculative verify: score the k drafts with
        the target in a single `forward_chunk(all_logits=True)` pass,
        run rejection sampling, and rewind BOTH caches' seq_lens to
        the accepted length. The chunk consumes [t0, d1, …, d_{k-1}]
        — exactly the tokens the drafter consumed — so target and
        drafter caches stay row-for-row in lockstep with no resync
        pass, and a full acceptance leaves ``dk`` as the pending
        token. Returns (cache, dcache, out_tokens (S, K), n_accept,
        n_emit, the slots' last tokens with the active slots' pending
        ones in)."""
        import jax
        import jax.numpy as jnp
        from analytics_zoo_tpu.ops.sampling import (sampling_probs,
                                                    speculative_accept)
        k = self.spec_k
        toks = jnp.concatenate([t0[:, None], drafts[:, :k - 1]],
                               axis=1)
        starts = cache.seq_lens
        n_new = jnp.where(active, k, 0).astype(jnp.int32)
        cache, all_logits = self.net.forward_chunk(
            params, cache, toks, starts, n_new, all_logits=True)
        p = sampling_probs(all_logits.astype(jnp.float32),
                           jnp.broadcast_to(temps[:, None],
                                            drafts.shape),
                           self.top_k)
        n_acc, corrected = speculative_accept(
            jax.random.fold_in(rng, step), p, qprobs, drafts)
        # emitted: the accepted prefix, then (on any rejection) the
        # corrected token; a full acceptance emits all k drafts and
        # keeps dk pending — in both cases the caches hold exactly
        # the consumed tokens, so the rewind is one where()
        n_emit = jnp.minimum(n_acc + 1, k)
        idx = jnp.arange(k, dtype=jnp.int32)[None, :]
        out = jnp.where(idx < n_acc[:, None], drafts,
                        corrected[:, None])
        nxt = jnp.where(n_acc == k, drafts[:, -1], corrected)
        new_len = starts + jnp.where(active, n_emit, 0)
        cache = cache._replace(
            seq_lens=jnp.where(active, new_len, cache.seq_lens))
        dcache = dcache._replace(
            seq_lens=jnp.where(active, new_len, dcache.seq_lens))
        return cache, dcache, out, n_acc, n_emit, \
            jnp.where(active, nxt, t0)

    def _compile(self, fn, structs, program, bucket=None,
                 donate=(0,)):
        """AOT-compile one engine program inside an
        `expected_compiles` bracket (deliberate warm/first-use
        compiles must not count toward the RecompileMonitor's storm
        window) + the usual span/counter."""
        import jax
        from analytics_zoo_tpu.common.diagnostics import \
            expected_compiles
        kw = {} if bucket is None else {"bucket": bucket}
        with expected_compiles(), \
                obs.span("decode/compile", program=program, **kw):
            compiled = jax.jit(
                fn, donate_argnums=donate).lower(*structs).compile()
        obs.counter(
            "zoo_tpu_serving_gen_compiles_total",
            help="generation programs compiled (warm-up only in "
            "steady state)", labels={"program": program}).inc()
        return compiled

    def _shape(self, *dims, dtype=np.int32):
        import jax
        return jax.ShapeDtypeStruct(tuple(dims), dtype)

    def _get_step(self):
        if self._compiled_step is None:
            s = self.max_slots
            structs = (
                self._abstract(self.cache),
                self._abstract(self.params),
                self._shape(s),
                self._shape(s, dtype=np.bool_),
                self._shape(s, dtype=np.float32),
                self._abstract(self._rng),
                self._shape(),
            )
            self._compiled_step = self._compile(
                self._step_fn, structs, "step", donate=(0, 2))
        return self._compiled_step

    def _get_prefill(self, tp: int):
        """The prefill program of bucket ``tp``: one prompt row,
        addressed by slot."""
        fn = self._compiled_prefill.get(tp)
        if fn is None:
            structs = (
                self._abstract(self.cache),
                self._abstract(self.params),
                self._shape(self.max_slots),
                self._shape(1, tp),
                self._shape(1),
                self._shape(1),
                self._shape(1, dtype=np.float32),
                self._abstract(self._rng),
                self._shape(),
            )
            fn = self._compile(self._prefill_fn, structs, "prefill",
                               bucket=tp, donate=(0, 2))
            self._compiled_prefill[tp] = fn
        return fn

    def _get_chunk(self):
        """The chunk program: one row of ``prefill_chunk`` tokens,
        addressed by slot."""
        if self._compiled_chunk is None:
            structs = (
                self._abstract(self.cache),
                self._abstract(self.params),
                self._shape(self.max_slots),
                self._shape(1, self.prefill_chunk),
                self._shape(1),
                self._shape(1),
                self._shape(1),
                self._shape(1, dtype=np.float32),
                self._abstract(self._rng),
                self._shape(),
            )
            self._compiled_chunk = self._compile(
                self._chunk_fn, structs, "chunk", donate=(0, 2))
        return self._compiled_chunk

    def _get_draft_prefill(self, tp: int):
        fn = self._compiled_draft_prefill.get(tp)
        if fn is None:
            structs = (
                self._abstract(self._draft_cache),
                self._abstract(self.drafter_params),
                self._shape(1, tp),
                self._shape(1),
                self._shape(1),
            )
            fn = self._compile(self._draft_prefill_fn, structs,
                               "draft_prefill", bucket=tp)
            self._compiled_draft_prefill[tp] = fn
        return fn

    def _get_draft_chunk(self):
        if self._compiled_draft_chunk is None:
            structs = (
                self._abstract(self._draft_cache),
                self._abstract(self.drafter_params),
                self._shape(1, self.prefill_chunk),
                self._shape(1),
                self._shape(1),
                self._shape(1),
            )
            self._compiled_draft_chunk = self._compile(
                self._draft_chunk_fn, structs, "draft_chunk")
        return self._compiled_draft_chunk

    def _get_draft(self):
        if self._compiled_draft is None:
            s = self.max_slots
            structs = (
                self._abstract(self._draft_cache),
                self._abstract(self.drafter_params),
                self._shape(s),
                self._shape(s, dtype=np.bool_),
                self._shape(s, dtype=np.float32),
                self._abstract(self._rng),
                self._shape(),
            )
            self._compiled_draft = self._compile(
                self._draft_fn, structs, "draft")
        return self._compiled_draft

    def _get_verify(self):
        if self._compiled_verify is None:
            s, k = self.max_slots, self.spec_k
            v = int(self.net.vocab)
            structs = (
                self._abstract(self.cache),
                self._abstract(self._draft_cache),
                self._abstract(self.params),
                self._shape(s),
                self._shape(s, k),
                self._shape(s, k, v, dtype=np.float32),
                self._shape(s, dtype=np.bool_),
                self._shape(s, dtype=np.float32),
                self._abstract(self._rng),
                self._shape(),
            )
            self._compiled_verify = self._compile(
                self._verify_fn, structs, "verify", donate=(0, 1))
        return self._compiled_verify

    def _handoff_export_fn(self, cache, page_ids):
        from analytics_zoo_tpu.ops import kv_cache as kvc
        return kvc.gather_slot_pages(cache, page_ids)

    def _handoff_import_fn(self, cache, last, page_ids, active, slot,
                           seq_len, last_token, k_rows, v_rows,
                           k_srows, v_srows):
        from analytics_zoo_tpu.ops import kv_cache as kvc
        cache = kvc.scatter_slot_pages(cache, page_ids, active, slot,
                                       seq_len, k_rows, v_rows,
                                       k_srows, v_srows)
        # the token the prefill side sampled, which the host alone
        # knows, goes to the slot's entry as a prefill's own does
        return cache, last.at[slot].set(last_token)

    def _handoff_row_structs(self):
        """(k/v rows, scale rows) ShapeDtypeStructs at the FIXED
        handoff width ``pages_per_slot`` — both handoff programs are
        shape-static over the full width (unused entries masked/
        dropped), so each compiles exactly once per engine."""
        lyr, _, page, width = self.cache.k_pages.shape
        p = self.pages_per_slot
        rows = self._shape(lyr, p, page, width,
                           dtype=self.cache.k_pages.dtype)
        if self.cache.k_scales is None:
            return rows, None
        return rows, self._shape(lyr, p, page,
                                 self.cache.k_scales.shape[3],
                                 dtype=np.float32)

    def _get_handoff_export(self):
        if self._compiled_handoff_export is None:
            structs = (
                self._abstract(self.cache),
                self._shape(self.pages_per_slot),
            )
            # read-only: the cache must survive the export (the
            # prefill engine keeps serving other slots), so nothing
            # is donated
            self._compiled_handoff_export = self._compile(
                self._handoff_export_fn, structs, "handoff_export",
                donate=())
        return self._compiled_handoff_export

    def _get_handoff_import(self):
        if self._compiled_handoff_import is None:
            p = self.pages_per_slot
            rows, srows = self._handoff_row_structs()
            structs = (
                self._abstract(self.cache),
                self._shape(self.max_slots),
                self._shape(p),
                self._shape(p, dtype=np.bool_),
                self._shape(),
                self._shape(),
                self._shape(),
                rows, rows, srows, srows,
            )
            self._compiled_handoff_import = self._compile(
                self._handoff_import_fn, structs, "handoff_import",
                donate=(0, 1))
        return self._compiled_handoff_import

    def _warmed(self) -> int:
        return (bool(self._compiled_step)
                + len(self._compiled_prefill)
                + bool(self._compiled_chunk)
                + len(self._compiled_draft_prefill)
                + bool(self._compiled_draft_chunk)
                + bool(self._compiled_draft)
                + bool(self._compiled_verify)
                + bool(self._compiled_handoff_export)
                + bool(self._compiled_handoff_import))

    def warm(self) -> int:
        """AOT-compile every program steady-state serving can need —
        the decode step, every prompt bucket's prefill (plus the
        drafter's, under speculation), the chunk programs (under
        chunked prefill), and the draft/verify pair — so the serving
        loop never compiles under traffic (the DynamicBatcher
        bucket-warm discipline). Returns the number of programs
        compiled this call. Idempotent."""
        n0 = self._warmed()
        # the first push of the page table compiles its conversion:
        # here, not under the first admission
        self._push_table()
        # role-gated: a prefill-pool engine never decodes (its only
        # steady-state programs are prefill/chunk + handoff export);
        # a decode-pool engine never sees a raw prompt (step + handoff
        # import). Monolithic "both" engines skip the handoff pair —
        # they never hand off, so they never pay those compiles.
        if self.role != "prefill":
            self._get_step()
        if self.role != "decode":
            for tp in self._warm_buckets():
                self._get_prefill(tp)
            if self.prefill_chunk > 0:
                self._get_chunk()
        if self.role == "prefill":
            self._get_handoff_export()
        if self.role == "decode":
            self._get_handoff_import()
        if self.spec_k > 0 and self.drafter is not None:
            self._get_draft()
            self._get_verify()
            if self.prefill_chunk > 0:
                self._get_draft_chunk()
            # prompts that fit in one chunk admit through the
            # whole-prompt path even when chunking is on (the
            # batcher routes them directly), so the drafter's
            # prefill buckets are steady-state programs regardless
            for tp in self._warm_buckets():
                self._get_draft_prefill(tp)
        return self._warmed() - n0

    def _warm_buckets(self) -> "tuple[int, ...]":
        """The prompt buckets steady-state serving reaches: all of
        them, or under chunked prefill those of prompts that fit one
        chunk (the batcher sends every longer prompt through
        :meth:`admit_partial`; a caller of :meth:`admit` that does
        not compiles a longer bucket at its first use)."""
        if self.prefill_chunk <= 0:
            return self.prompt_buckets
        top = self.prompt_bucket(min(self.prefill_chunk,
                                     self.prompt_buckets[-1]))
        return tuple(b for b in self.prompt_buckets if b <= top)

    # -- admission / stepping / retirement ----------------------------------
    def pages_for(self, prompt_len: int, max_new: int) -> int:
        """Worst-case page reservation for one request (prompt +
        max_new tokens, capped at the context window)."""
        from analytics_zoo_tpu.ops.kv_cache import PageAllocator
        return PageAllocator.pages_needed(
            min(prompt_len + max_new, self.max_context),
            self.page_size)

    def can_admit(self, prompt_len: int, max_new: int) -> bool:
        """Whether a request of this size fits RIGHT NOW: a free slot
        and enough free pages for its worst case. Pages are reserved
        in full at admission (prompt + max_new tokens), so an admitted
        sequence can always run to completion — no mid-decode
        eviction, no allocation deadlock."""
        return bool(self.free_slots) and self.allocator.can_alloc(
            self.pages_for(prompt_len, max_new))

    def prompt_bucket(self, prompt_len: int) -> int:
        """The padded length a prompt of ``prompt_len`` prefills at."""
        return next(b for b in self.prompt_buckets if b >= prompt_len)

    def admit(self, requests: "Sequence[tuple]") -> "list[tuple]":
        """:meth:`admit_dispatch`, then the first tokens fetched
        together: ``[(slot, first_token), ...]``."""
        if not requests:
            return []
        h = self.admit_dispatch(requests)
        return list(zip(h.slots, self.collect(h).tolist()))

    def admit_dispatch(self, requests: "Sequence[tuple]"
                       ) -> Dispatched:
        """Admit ``[(prompt_ids, max_new, temperature), ...]`` into
        free slots of the LIVE batch: assign pages, write the table
        rows (one push), then run ONE one-row prefill a request, at
        that request's own bucket and addressed to its slot — the
        programs compute the admitted prompts and nothing else, and
        touch no other slot (the property `prefill` guarantees) —
        and sample each new slot's first token, which the program
        leaves in the slot's entry of the device's last tokens: a
        step dispatched next consumes it with no fetch between.
        Returns the handle whose ``slots`` are the admitted slots
        and whose :meth:`collect` gives their first tokens, and
        leaves ``(calls, rows)`` of this admission in
        :attr:`prefill_counts`. Raises MemoryError when slots/pages
        run out mid-list (callers gate with :meth:`can_admit` per
        request first)."""
        for prompt_ids, _, _ in requests:
            if not 1 <= len(prompt_ids) <= self.max_context - 1:
                raise ValueError(
                    f"prompt length {len(prompt_ids)} outside [1, "
                    f"{self.max_context - 1}]")
        admitted = [self._claim_slot(*r) for r in requests]
        self._push_table()
        firsts, rows = [], 0
        t0 = time.perf_counter()
        for slot, (prompt_ids, _, _) in zip(admitted, requests):
            n = len(prompt_ids)
            tp = self.prompt_bucket(n)
            ids = np.zeros((1, tp), np.int32)
            ids[0, :n] = np.asarray(prompt_ids, np.int32)
            plens = np.full((1,), n, np.int32)
            at = np.full((1,), slot, np.int32)
            # (a copy of the temperature: the host's vector is
            # rewritten by later admissions while programs are queued)
            self.cache, self._last_tok, tok = self._get_prefill(tp)(
                self.cache, self.params, self._last_tok, ids, plens,
                at, self._temps[slot:slot + 1].copy(), self._rng,
                np.int32(self._step_id))
            self._step_id += 1
            firsts.append(tok)
            rows += ids.shape[0]
            if self._draft_cache is not None:
                self._draft_cache = self._get_draft_prefill(tp)(
                    self._draft_cache, self.drafter_params, ids,
                    plens, at)
        self.prefill_counts = (len(firsts), rows)
        return Dispatched(firsts, 1, admitted,
                          dispatch_s=time.perf_counter() - t0)

    def _claim_slot(self, prompt_ids, max_new, temperature) -> int:
        """Allocate pages + a slot + its table row for one request
        (shared by whole-prompt and chunked admission)."""
        from analytics_zoo_tpu.ops.kv_cache import PageAllocator
        n = len(prompt_ids)
        need = PageAllocator.pages_needed(
            min(n + int(max_new), self.max_context), self.page_size)
        if not self.free_slots:
            raise MemoryError("no free decode slot")
        pages = self.allocator.alloc(need)  # MemoryError if short
        slot = min(self.free_slots)
        self.free_slots.discard(slot)
        self._slot_pages[slot] = pages
        row = np.full((self.pages_per_slot,), pages[-1], np.int32)
        row[:need] = pages
        self._table[slot] = row
        self._temps[slot] = float(temperature)
        return slot

    def _push_table(self):
        """Publish the host table to BOTH device caches (the drafter
        mirrors the target's page placement by construction). Each
        cache gets its OWN device copy: the compiled programs donate
        whole cache pytrees, and a buffer shared across the two would
        be deleted under the survivor's feet."""
        import jax
        self.cache = self.cache._replace(
            page_table=jax.numpy.array(self._table))
        if self._draft_cache is not None:
            self._draft_cache = self._draft_cache._replace(
                page_table=jax.numpy.array(self._table))

    # -- chunked prefill ----------------------------------------------------
    def admit_partial(self, requests: "Sequence[tuple]"
                      ) -> "list[int]":
        """Chunked admission: assign each request a slot, pages and a
        table row — but run NO forward pass. The prompt is parked in
        the chunk scheduler and :meth:`prefill_step` feeds it to the
        cache ``prefill_chunk`` tokens at a time, interleaved with
        decode iterations by the batcher. Returns the slots (first
        tokens arrive from the prefill_step that lands each prompt's
        final chunk). Same gating contract as :meth:`admit`."""
        if self.prefill_chunk <= 0:
            raise ValueError("admit_partial needs prefill_chunk > 0")
        for prompt_ids, _, _ in requests:
            if not 1 <= len(prompt_ids) <= self.max_context - 1:
                raise ValueError(
                    f"prompt length {len(prompt_ids)} outside [1, "
                    f"{self.max_context - 1}]")
        slots = []
        for prompt_ids, max_new, temperature in requests:
            slot = self._claim_slot(prompt_ids, max_new, temperature)
            self._pending_prompts[slot] = [
                np.asarray(prompt_ids, np.int32), 0]
            slots.append(slot)
        if slots:
            self._push_table()
        return slots

    @property
    def prefilling_slots(self) -> "set[int]":
        """Slots admitted via :meth:`admit_partial` whose prompts are
        not yet fully cached (must NOT take decode steps)."""
        return set(self._pending_prompts)

    def cancel_prefill(self, slot: int):
        """Drop a mid-prefill slot (drain/cancel): forget its pending
        prompt; the caller releases pages via :meth:`release` as
        usual. Rows its finished chunks wrote are dead — seq_lens
        stops advancing and a future occupant overwrites them."""
        self._pending_prompts.pop(slot, None)

    def prefill_step(self) -> "list[tuple]":
        """:meth:`prefill_dispatch`, then the chunk's token (and
        counts) fetched: ``[(slot, first_token)]`` if the chunk was
        its prompt's last (the slot then decodes), else []."""
        h = self.prefill_dispatch()
        if h is None:
            return []
        return list(zip(h.slots, self.collect(h).tolist()))

    def prefill_dispatch(self) -> "Dispatched | None":
        """Advance ONE prefilling slot by one chunk (at most
        ``prefill_chunk`` prompt tokens): the compiled one-row chunk
        program, addressed to the slot, so a chunk computes the
        tokens it writes and no idle slot's padding. The slots take
        turns in the order they were admitted, a slot with prompt
        left going to the back, so a call costs one chunk program
        however many prompts are mid-prefill. Returns the chunk's
        handle — ``slots`` holds the slot if the chunk was its
        prompt's last (its first token then stands in the device's
        last tokens and the slot decodes), else nothing — and leaves
        ``(slot, start, tokens)`` of the chunk in :attr:`chunk_work`;
        None, and ``chunk_work`` None, when nothing is prefilling."""
        self.chunk_work = None
        if not self._pending_prompts:
            return None
        c = self.prefill_chunk
        slot = next(iter(self._pending_prompts))
        ids, off = st = self._pending_prompts.pop(slot)
        n = min(c, len(ids) - off)
        row = np.zeros((1, c), np.int32)
        row[0, :n] = ids[off:off + n]
        starts = np.full((1,), off, np.int32)
        n_new = np.full((1,), n, np.int32)
        at = np.full((1,), slot, np.int32)
        t0 = time.perf_counter()
        self.cache, self._last_tok, tok = self._get_chunk()(
            self.cache, self.params, self._last_tok, row, starts,
            n_new, at, self._temps[slot:slot + 1].copy(), self._rng,
            np.int32(self._step_id))
        self._step_id += 1
        if self._draft_cache is not None:
            self._draft_cache = self._get_draft_chunk()(
                self._draft_cache, self.drafter_params, row, starts,
                n_new, at)
        self.chunk_work = (slot, off, n)
        dispatch_s = time.perf_counter() - t0
        if off + n < len(ids):
            st[1] = off + n
            self._pending_prompts[slot] = st    # to the back
            return Dispatched([tok], 1, dispatch_s=dispatch_s)
        return Dispatched([tok], 1, [slot], dispatch_s=dispatch_s)

    def step(self, active: np.ndarray) -> np.ndarray:
        """One decode iteration over the WHOLE slot array, start to
        tokens: :meth:`dispatch`, then :meth:`collect`. Returns the
        ``(max_slots,)`` sampled tokens — meaningful only at active
        slots."""
        return self.collect(self.dispatch(active))

    def dispatch(self, active: np.ndarray) -> Dispatched:
        """Start one decode iteration over the WHOLE slot array:
        append each active slot's last token to the cache, attend,
        sample. Slots with ``active == False`` are frozen (nothing
        written, lengths unchanged, last token kept). The sampled
        tokens become the slots' last tokens ON THE DEVICE, so the
        next dispatch needs nothing this one produces: a caller may
        dispatch step k + 1 before it collects step k."""
        _STEP_FAULT.fire()
        fn = self._get_step()
        active = np.asarray(active, np.bool_)
        t0 = time.perf_counter()
        self.cache, self._last_tok, toks = fn(
            self.cache, self.params, self._last_tok, active,
            self._temps.copy(), self._rng, np.int32(self._step_id))
        self._step_id += 1
        return Dispatched([toks], self.max_slots,
                          dispatch_s=time.perf_counter() - t0)

    def collect(self, h: Dispatched) -> np.ndarray:
        """Fetch a handle's tokens (blocking until its programs have
        run): a step's ``(max_slots,)`` vector, or one first token a
        program of an admission or a chunk. The counts that ride
        behind them go to the net's ``record_step_counts``."""
        import jax
        t0 = time.perf_counter()
        got = jax.device_get(h.outs)
        h.fetch_s = time.perf_counter() - t0
        for a in got:
            if len(a) > h.width:
                self.net.record_step_counts(a[h.width:])
        return np.concatenate([a[:h.width] for a in got])

    def spec_step(self, active: np.ndarray):
        """One speculative round over the active slots: draft
        ``spec_k`` tokens with the drafter (one compiled scan), then
        verify them against the target in one compiled chunk pass
        with rejection sampling. Returns ``(out_tokens (S, K),
        n_emit (S,))`` — slot s emitted ``out_tokens[s, :n_emit[s]]``
        this round (1..K tokens; inactive slots emit 0). Callers must
        only include slots whose remaining token budget AND context
        window can absorb K tokens (the batcher gates this)."""
        _STEP_FAULT.fire()
        active = np.asarray(active, np.bool_)
        dfn, vfn = self._get_draft(), self._get_verify()
        temps = self._temps.copy()
        t0 = time.perf_counter()
        self._draft_cache, drafts, qprobs = dfn(
            self._draft_cache, self.drafter_params, self._last_tok,
            active, temps, self._rng, np.int32(self._step_id))
        self._step_id += 1
        (self.cache, self._draft_cache, out, n_acc, n_emit,
         self._last_tok) = vfn(
            self.cache, self._draft_cache, self.params,
            self._last_tok, drafts, qprobs, active, temps, self._rng,
            np.int32(self._step_id))
        self._step_id += 1
        t1 = time.perf_counter()
        out = np.asarray(out)
        n_emit = np.where(active, np.asarray(n_emit), 0)
        n_active = int(active.sum())
        self.spec_proposed += self.spec_k * n_active
        self.spec_accepted += int(
            np.asarray(n_acc)[active].sum()) if n_active else 0
        self.spec_times = (t1 - t0, time.perf_counter() - t1)
        return out, n_emit

    def release(self, slot: int):
        """Retire a slot: reclaim its pages and return it to the free
        pool. The cache rows need no reset — a future `prefill` with
        ``prompt_lens > 0`` overwrites ``seq_lens``, and until then
        the ``active`` mask keeps the slot frozen. A slot still
        mid-chunked-prefill is cancelled (its pending prompt
        dropped), so cancel/drain leaks neither pages nor scheduler
        state."""
        self._pending_prompts.pop(slot, None)
        pages = self._slot_pages.pop(slot, None)
        if pages:
            self.allocator.free(pages)
        self.free_slots.add(slot)

    # -- prefill/decode handoff ---------------------------------------------
    def export_handoff(self, slot: int) -> dict:
        """Extract an active slot's cache state into a handoff blob
        and retire the slot (pages reclaimed immediately — the
        prefill pool's capacity frees the moment the blob exists;
        exactly-once on a lost blob is the router's job, via
        re-prefill from the original prompt). The blob carries the
        used pages of every layer (int8 scales included), the
        position, the last sampled token, and the slot's sampling
        temperature — everything :meth:`admit_from_handoff` needs to
        resume decode token-exactly with NO forward pass."""
        import jax
        from analytics_zoo_tpu.ops import kv_cache as kvc
        kvc.refuse_row_handoff(self.cache)
        if slot in self._pending_prompts:
            raise ValueError(
                f"slot {slot} is still mid-chunked-prefill")
        if slot in self.free_slots:
            raise ValueError(f"slot {slot} is not active")
        seq_len = int(np.asarray(self.cache.seq_lens)[slot])
        if seq_len <= 0:
            raise ValueError(f"slot {slot} has no cached tokens")
        n_used = kvc.PageAllocator.pages_needed(seq_len,
                                                self.page_size)
        fn = self._get_handoff_export()
        k, v, k_s, v_s = fn(self.cache,
                            jax.numpy.asarray(self._table[slot]))
        blob = {
            "version": kvc.HANDOFF_VERSION,
            "seq_len": seq_len,
            "page_size": self.page_size,
            "kv_dtype": np.dtype(self.cache.k_pages.dtype).name,
            "num_layers": int(self.cache.k_pages.shape[0]),
            "row_width": int(self.cache.k_pages.shape[3]),
            "last_token": int(np.asarray(self._last_tok)[slot]),
            "temperature": float(self._temps[slot]),
            "k": np.asarray(k)[:, :n_used].copy(),
            "v": np.asarray(v)[:, :n_used].copy(),
            "k_scales": (None if k_s is None
                         else np.asarray(k_s)[:, :n_used].copy()),
            "v_scales": (None if v_s is None
                         else np.asarray(v_s)[:, :n_used].copy()),
        }
        self.release(slot)
        return blob

    def _check_handoff_blob(self, blob: dict):
        from analytics_zoo_tpu.ops import kv_cache as kvc
        kvc.refuse_row_handoff(self.cache)
        if int(blob.get("version", -1)) != kvc.HANDOFF_VERSION:
            raise ValueError(
                f"handoff version {blob.get('version')!r} != "
                f"{kvc.HANDOFF_VERSION}")
        mine = {
            "page_size": self.page_size,
            "kv_dtype": np.dtype(self.cache.k_pages.dtype).name,
            "num_layers": int(self.cache.k_pages.shape[0]),
            "row_width": int(self.cache.k_pages.shape[3]),
        }
        for key, want in mine.items():
            if blob.get(key) != want:
                raise ValueError(
                    f"handoff {key} mismatch: blob has "
                    f"{blob.get(key)!r}, engine has {want!r}")
        seq_len = int(blob["seq_len"])
        if not 1 <= seq_len <= self.max_context - 1:
            raise ValueError(
                f"handoff seq_len {seq_len} outside [1, "
                f"{self.max_context - 1}]")

    def admit_from_handoff(self, blob: dict, max_new: int) -> int:
        """Splice a handoff blob into this engine: claim a slot +
        pages (the same worst-case reservation :meth:`admit` makes,
        with the blob's position standing in for the prompt length),
        scatter the shipped pages into the freshly allocated physical
        pages, and restore the resume state — NO forward pass runs.
        The very next :meth:`step` with this slot active appends the
        blob's ``last_token`` and continues the stream token-exactly.
        Validation happens before any allocation, so a rejected blob
        leaves the engine untouched (the router refunds it to a
        sibling). Returns the claimed slot."""
        import jax
        from analytics_zoo_tpu.ops import kv_cache as kvc
        self._check_handoff_blob(blob)
        seq_len = int(blob["seq_len"])
        n_used = kvc.PageAllocator.pages_needed(seq_len,
                                                self.page_size)
        need = kvc.PageAllocator.pages_needed(
            min(seq_len + int(max_new), self.max_context),
            self.page_size)
        if not self.free_slots:
            raise MemoryError("no free decode slot")
        pages = self.allocator.alloc(need)  # MemoryError if short
        slot = min(self.free_slots)
        self.free_slots.discard(slot)
        self._slot_pages[slot] = pages
        row = np.full((self.pages_per_slot,), pages[-1], np.int32)
        row[:need] = pages
        self._table[slot] = row
        self._temps[slot] = float(blob["temperature"])
        self._push_table()
        p = self.pages_per_slot
        active = np.zeros((p,), np.bool_)
        active[:n_used] = True

        def pad(a):
            if a is None:
                return None
            out = np.zeros((a.shape[0], p) + a.shape[2:], a.dtype)
            out[:, :n_used] = a
            return out

        fn = self._get_handoff_import()
        self.cache, self._last_tok = fn(
            self.cache, self._last_tok, jax.numpy.asarray(row),
            active, np.int32(slot), np.int32(seq_len),
            np.int32(blob["last_token"]), pad(blob["k"]),
            pad(blob["v"]), pad(blob["k_scales"]),
            pad(blob["v_scales"]))
        return slot

    @property
    def slots_active(self) -> int:
        return self.max_slots - len(self.free_slots)

    @property
    def free_pages(self) -> int:
        return self.allocator.free_pages

    # -- sequential whole-loop path -----------------------------------------
    def generate(self, prompts, max_new_tokens: int = 32, *,
                 temperature: float = 0.0, eos_id=None, rng=None
                 ) -> "list[np.ndarray]":
        """Per-request compiled generation: the model's whole-loop
        `generate` (prefill + `lax.while_loop`), jit-cached per
        (batch, prompt-bucket, max_new) shape. This is the SEQUENTIAL
        baseline — each call owns a fresh cache and runs to
        completion; concurrent traffic should go through the
        continuous batcher instead. Returns one array of NEWLY
        generated token ids per prompt (eos, when hit, included)."""
        import jax
        if prompts and np.isscalar(prompts[0]):
            prompts = [prompts]
        s = len(prompts)
        tp = max(len(p) for p in prompts)
        tp = next((b for b in self.prompt_buckets if b >= tp), tp)
        max_new = int(max_new_tokens)
        ids = np.zeros((s, tp), np.int32)
        plens = np.zeros((s,), np.int32)
        for i, p in enumerate(prompts):
            ids[i, :len(p)] = np.asarray(p, np.int32)
            plens[i] = len(p)
        key = (s, tp, max_new, eos_id)
        fn = self._gen_jits.get(key)
        if fn is None:
            net, tk = self.net, self.top_k
            ps, cd = self.page_size, self.cache_dtype

            def run(params, ids, plens, temps, rng):
                return net.generate(
                    params, ids, prompt_lens=plens,
                    max_new_tokens=max_new, temperature=temps,
                    top_k=tk, eos_id=eos_id, rng=rng,
                    page_size=ps, cache_dtype=cd)

            fn = jax.jit(run)
            self._gen_jits[key] = fn
        temps = np.full((s,), float(temperature), np.float32)
        buf, lens = fn(self.params, ids, plens, temps,
                       self._rng if rng is None else rng)
        buf, lens = np.asarray(buf), np.asarray(lens)
        return [buf[i, plens[i]:lens[i]] for i in range(s)]

    def stats(self) -> dict:
        """JSON-able summary for ``GET /health``."""
        out = {
            "role": self.role,
            "max_slots": self.max_slots,
            "slots_active": self.slots_active,
            "max_context": self.max_context,
            "page_size": self.page_size,
            "free_pages": self.free_pages,
            "total_pages": self.allocator.max_pages,
            "prompt_buckets": list(self.prompt_buckets),
            "warmed_programs": self._warmed(),
            "kv_dtype": np.dtype(self.cache.pool_dtype).name,
            "prefill_chunk": self.prefill_chunk,
            "spec_k": self.spec_k,
        }
        if self.spec_k > 0:
            out["spec_proposed"] = self.spec_proposed
            out["spec_accepted"] = self.spec_accepted
            out["spec_accept_rate"] = (
                self.spec_accepted / self.spec_proposed
                if self.spec_proposed else None)
        return out

    def __repr__(self):
        return (f"GenerationEngine(slots={self.max_slots}, "
                f"context={self.max_context}, "
                f"page_size={self.page_size}, "
                f"free_pages={self.free_pages})")
