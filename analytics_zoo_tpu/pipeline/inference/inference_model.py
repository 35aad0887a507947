"""InferenceModel (L9): thread-safe serving wrapper.

Reference: `Z/pipeline/inference/InferenceModel.scala:29-120` — a
`LinkedBlockingQueue` of `supportedConcurrentNum` weight-sharing model
copies with loaders for BigDL/Caffe/TF/OpenVINO backends.

TPU-native redesign:
- the blocking pool is the native C++ queue (`native/serving_queue.cpp`),
  holding slot ids; each slot is a *compiled executable* reference —
  XLA-compiled programs are reentrant, so slots share one executable
  (the exact analog of the reference's weight-sharing clones,
  `FloatModel.scala:73-87`);
- OpenVINO's accelerated-inference role is played by XLA ahead-of-time
  compilation: `load_*` lowers + compiles the forward at load time for
  the declared input shapes;
- TF models load via a frozen `tf.function` bridged into XLA
  (`jax2tf.call_tf`) — the TFNet serving path without a JNI session.
"""

from __future__ import annotations

import functools
import json
import threading
import zipfile
from typing import Callable, Optional, Sequence, Tuple

import jax
import numpy as np

from analytics_zoo_tpu.common import observability as obs
from analytics_zoo_tpu.common.nncontext import logger
from analytics_zoo_tpu.native import make_serving_queue

_ARTIFACT_VERSION = 1


def _tree_spec(skel) -> dict:
    """JSON-able structure spec of a pytree SKELETON (leaves are
    ints). The artifact stores this instead of pickled PyTreeDefs so
    the tree metadata adds no unpickling surface of its own. NOTE the
    executable blob itself still deserializes through jax's
    pickle-based loader — see the trust-model note on
    :meth:`InferenceModel.load_compiled`."""
    if isinstance(skel, tuple):
        return {"t": "tuple", "c": [_tree_spec(c) for c in skel]}
    if isinstance(skel, list):
        return {"t": "list", "c": [_tree_spec(c) for c in skel]}
    if isinstance(skel, dict):
        keys = sorted(skel)
        return {"t": "dict", "k": keys,
                "c": [_tree_spec(skel[k]) for k in keys]}
    if skel is None:
        return {"t": "none"}
    return {"t": "leaf"}


def _tree_from_spec(spec: dict):
    t = spec["t"]
    if t == "tuple":
        return tuple(_tree_from_spec(c) for c in spec["c"])
    if t == "list":
        return [_tree_from_spec(c) for c in spec["c"]]
    if t == "dict":
        return {k: _tree_from_spec(c)
                for k, c in zip(spec["k"], spec["c"])}
    if t == "none":
        return None
    return 0


class _WeightsAsArgument:
    """``jit(pure_fn)`` with the weights bound as its first ARGUMENT.

    A jitted closure over the weights embeds them in the program as
    constants: every executable compiled from it (the AOT one and one
    per batch bucket) then carries its own copy — in device memory,
    in a serialized executable, in the compilation cache (102 MB each
    for ResNet-50). Bound as an argument, all of them share the one
    resident copy. Same surface as the jitted function where
    `InferenceModel` uses it: call it, or ``lower(*args).compile()``.
    """

    def __init__(self, pure_fn: Callable, params):
        self._jit = jax.jit(pure_fn)
        self._params = params

    def __call__(self, *xs):
        return self._jit(self._params, *xs)

    def lower(self, *args):
        return _BoundLowered(self._jit.lower(self._params, *args),
                             self._params)


class _BoundLowered:
    def __init__(self, lowered, params):
        self._lowered = lowered
        self._params = params

    def compile(self):
        return functools.partial(self._lowered.compile(),
                                 self._params)


class InferenceModel:
    def __init__(self, supported_concurrent_num: int = 1):
        self.supported_concurrent_num = int(supported_concurrent_num)
        self._queue = make_serving_queue()
        self._predict_fn: Optional[Callable] = None
        self._export_src: Optional[Tuple] = None
        self._compiled = False
        self._trace_fn: Optional[Callable] = None
        self._example_specs = None  # [(shape, np.dtype)] when known
        self._generation = 0
        self._lock = threading.Lock()
        self.quantized = None  # QuantizedModel when loaded with int8
        self._generator = None  # GenerationEngine via load_generator

    # -- loaders ------------------------------------------------------------
    def _install(self, predict_fn: Optional[Callable],
                 example_inputs: Optional[Sequence[np.ndarray]] = None,
                 export_state: Optional[Tuple] = None):
        # a plain model arrives as ``(params, pure_fn)`` and serves
        # with its weights as an argument; programs that embed their
        # own state (int8 tables, a bridged TF graph) stay closures
        jfn = (jax.jit(predict_fn) if export_state is None
               else _WeightsAsArgument(export_state[1],
                                       export_state[0]))
        fn = jfn
        if example_inputs is not None:
            # AOT-compile for the declared shapes (the OpenVINO-IR role)
            fn = jfn.lower(*example_inputs).compile()
        # kept for export_compiled: ``(params_pytree, pure_fn)`` —
        # the pure form lets export re-commit the weights to ONE
        # device and stage a single-device artifact program,
        # independent of this process's mesh (a serving process is
        # one chip; a program lowered against mesh-committed params
        # would demand the exporter's device count from every loader)
        specs = None
        if example_inputs is not None:
            specs = [(tuple(np.shape(e)), np.asarray(e).dtype)
                     for e in example_inputs]
        self._swap_model(fn, compiled=example_inputs is not None,
                         export_src=(export_state, example_inputs),
                         trace_fn=jfn, example_specs=specs)

    def _swap_model(self, fn, compiled: bool, export_src,
                    trace_fn=None, example_specs=None):
        """Atomically install (fn, compiled-flag, fresh slot pool):
        predict() snapshots all three under the same lock, so a
        reload can never pair a new executable with a stale
        conversion flag. The queue is REPLACED, not drained —
        draining could not reclaim slots held by in-flight predicts,
        whose returns would then inflate the pool; a stale slot lands
        in the retired queue and is forgotten. (Predicts that took a
        slot from the retired queue finish against the old fn; for
        one reload window total concurrency may transiently exceed
        the contract by those stragglers.)"""
        q = make_serving_queue()
        for slot in range(self.supported_concurrent_num):
            q.put(slot)
        with self._lock:
            self._predict_fn = fn
            self._compiled = compiled
            self._export_src = export_src
            self._trace_fn = trace_fn
            self._example_specs = example_specs
            self._generation += 1
            self._queue = q

    def load(self, model_path: str,
             example_inputs: Optional[Sequence] = None,
             quantize: bool = False):
        """Load a saved ZooModel (`ZooModel.save_model` output) —
        the `doLoad` BigDL path. ``quantize=True`` serves int8 (the
        reference's quantized-inference claim, wp-bigdl.md:192-196;
        requires example_inputs for calibration)."""
        from analytics_zoo_tpu.models.common import ZooModel
        zm = ZooModel.load_model(model_path)
        return self.load_keras_net(zm.model,
                                   example_inputs=example_inputs,
                                   quantize=quantize)

    def load_keras_net(self, net, params=None,
                       example_inputs: Optional[Sequence] = None,
                       quantize: bool = False,
                       quantize_types: Optional[Sequence[str]] = None):
        """Serve an in-memory KerasNet; ``quantize=True`` swaps Dense
        kernels for int8 (MXU 8-bit path) calibrated on
        ``example_inputs``. ``quantize_types`` widens the layer set
        (e.g. ``("Dense", "Convolution2D")`` — conv int8 is measured
        slower than bf16 on v5e but 4x smaller; see
        `inference/quantize.py`)."""
        if params is None:
            est = net.estimator
            if est.params is None:
                est._ensure_initialized()
            params = est.params

        if quantize:
            if example_inputs is None:
                raise ValueError(
                    "quantize=True needs example_inputs for "
                    "activation-scale calibration")
            from analytics_zoo_tpu.pipeline.inference.quantize import \
                QuantizedModel
            kw = {} if quantize_types is None else \
                {"quantize_types": tuple(quantize_types)}
            qm = QuantizedModel(net, params,
                                np.asarray(example_inputs[0]), **kw)
            self.quantized = qm

            def predict_fn(*xs):
                return qm.forward(xs[0] if len(xs) == 1 else list(xs))
            export_state = None  # int8 tables live inside qm
        else:
            self.quantized = None
            predict_fn = None  # served as pure_fn(params, *xs)

            def pure_fn(p, *xs):
                x = list(xs) if len(xs) > 1 else xs[0]
                return net.forward(p, x, training=False)
            export_state = (params, pure_fn)

        self._install(predict_fn,
                      None if example_inputs is None
                      else [np.asarray(e) for e in example_inputs],
                      export_state=export_state)
        return self

    def load_tf(self, saved_model_path: str,
                example_inputs: Optional[Sequence] = None,
                signature: str = "serving_default"):
        """TF SavedModel → XLA (the `doLoadTF` path,
        InferenceModel.scala:69, without the TFNet JNI session)."""
        from analytics_zoo_tpu.pipeline.api.net import TFNet
        net = TFNet.from_saved_model(saved_model_path,
                                     signature=signature)

        def predict_fn(*xs):
            return net(*xs)

        self._install(predict_fn,
                      None if example_inputs is None
                      else [np.asarray(e) for e in example_inputs])
        return self

    def load_openvino(self, model_path: str, weight_path=None,
                      **kwargs):
        """Deprecated delegating shim (reference
        `InferenceModel.scala:69-120` `doLoadOpenVINO`): the
        OpenVINO-IR role — an on-disk ahead-of-time compiled serving
        artifact any process can load — is played by
        :meth:`export_compiled` / :meth:`load_compiled` XLA bundles.
        ``model_path`` must point at an ``export_compiled`` artifact;
        ``weight_path`` is ignored (weights are embedded).

        TRUST MODEL: migrated call sites must know the error surface
        changed — an OpenVINO IR load fails safely on a bad file, but
        this shim delegates to :meth:`load_compiled`, whose
        executable blob deserializes through jax's pickle-based
        loader and runs with the loader's privileges. Load artifacts
        only from sources you trust."""
        import warnings
        warnings.warn(
            "load_openvino is deprecated on the TPU-native stack; "
            "pass an export_compiled() artifact (delegating to "
            "load_compiled — which deserializes the executable blob "
            "through jax's pickle-based loader: load artifacts only "
            "from sources you trust)", DeprecationWarning,
            stacklevel=2)
        return self.load_compiled(model_path)

    # -- serialized AOT artifact (the OpenVINO-IR role) ---------------------
    def export_compiled(self, path: str) -> str:
        """Write the AOT-compiled serving program to ``path`` (a zip
        bundle) that another process loads with :meth:`load_compiled`
        and serves WITHOUT recompiling — the on-disk-IR property of
        the reference's OpenVINO backend
        (`OpenVinoInferenceSupportive.scala:69-155`).

        The bundle carries two encodings:
        - ``executable.bin``: the serialized XLA executable (weights
          embedded as program constants) — loads with zero
          compilation on a machine/backend matching the exporter;
        - ``export.bin``: the portable ``jax.export`` StableHLO blob —
          the cross-machine fallback, compiled once at load time
          (still no Python model code or retracing needed).

        Requires a model loaded with ``example_inputs`` (AOT)."""
        from jax.experimental import serialize_executable as se

        if not self._compiled or self._export_src is None or \
                self._export_src[1] is None:
            raise RuntimeError(
                "export_compiled needs a model loaded with "
                "example_inputs (the AOT pre-compile path)")
        export_state, examples = self._export_src
        if export_state is None:
            raise NotImplementedError(
                "export_compiled supports load/load_keras_net models "
                "(quantized and call_tf-bridged programs embed state "
                "the exporter cannot re-stage single-device yet)")
        params, pure_fn = export_state
        # the ARTIFACT program is staged single-device: a serving
        # process is one chip, and a program lowered against this
        # process's mesh (training params are often replicated across
        # it) would demand the same device count from every loader.
        # Re-committing the weights to one device is what makes the
        # lowering single-device; the in-memory pool (_predict_fn)
        # keeps its mesh-aware form.
        dev = jax.devices()[0]
        p1 = jax.device_put(
            params, jax.sharding.SingleDeviceSharding(dev))

        def fn1(*xs):
            return pure_fn(p1, *xs)

        sjit = jax.jit(fn1)
        with jax.default_device(dev):
            payload, in_tree, out_tree = se.serialize(
                sjit.lower(*examples).compile())
        in_skel = jax.tree_util.tree_unflatten(
            in_tree, list(range(in_tree.num_leaves)))
        out_skel = jax.tree_util.tree_unflatten(
            out_tree, list(range(out_tree.num_leaves)))
        from jax import export as jexport
        # the portable blob is lowered for the exporter's platform
        # AND cpu, so a cpu serving box can still load a TPU-exported
        # artifact
        plats = list(dict.fromkeys([jax.default_backend(), "cpu"]))
        try:
            exported = jexport.export(sjit, platforms=plats)(*examples)
        except Exception:  # multi-platform lowering unsupported here
            plats = [plats[0]]
            exported = jexport.export(sjit)(*examples)
        export_blob = exported.serialize()
        # batch-polymorphic variant (leading dim symbolic): lets a
        # loading process re-specialize the program for OTHER batch
        # sizes — what DynamicBatcher's bucket warming needs from a
        # load_compiled model. Optional: not every program lowers
        # under a symbolic batch dim.
        poly_blob = None
        try:
            (b,) = jexport.symbolic_shape("b")
            pargs = [jax.ShapeDtypeStruct(
                (b,) + tuple(np.shape(e))[1:],
                np.asarray(e).dtype) for e in examples]
            poly_blob = jexport.export(
                sjit, platforms=plats)(*pargs).serialize()
        except Exception as e:
            logger.info("batch-polymorphic export unavailable "
                        "(%s: %s); artifact serves its declared "
                        "batch only", type(e).__name__, e)
        meta = {
            "version": _ARTIFACT_VERSION,
            "platform": jax.default_backend(),
            "export_platforms": plats,
            "jax_version": jax.__version__,
            "n_devices": 1,
            "in_spec": _tree_spec(in_skel),
            "out_spec": _tree_spec(out_skel),
            "inputs": [{"shape": list(np.shape(e)),
                        "dtype": str(np.asarray(e).dtype)}
                       for e in examples],
        }
        with zipfile.ZipFile(path, "w") as z:
            z.writestr("meta.json", json.dumps(meta))
            z.writestr("executable.bin", payload)
            z.writestr("export.bin", export_blob)
            if poly_blob is not None:
                z.writestr("export_poly.bin", poly_blob)
        logger.info("exported compiled serving artifact -> %s "
                    "(%d inputs, platform=%s)", path,
                    len(meta["inputs"]), meta["platform"])
        return path

    def load_compiled(self, path: str):
        """Load an :meth:`export_compiled` bundle and serve it. On a
        matching machine/backend the serialized executable loads
        directly — NO compilation, no tracing, no model code; on a
        different one the portable ``jax.export`` blob is compiled
        once for the declared shapes (lowered at export for the
        exporter's platform and cpu).

        TRUST MODEL: like any executable format (an OpenVINO IR, a
        shared library), a bundle runs with the loader's privileges —
        the executable blob deserializes through jax's pickle-based
        loader. Load artifacts only from sources you trust."""
        from jax.experimental import serialize_executable as se

        with zipfile.ZipFile(path, "r") as z:
            meta = json.loads(z.read("meta.json").decode())
            exec_blob = z.read("executable.bin")
            export_blob = z.read("export.bin")
            poly_blob = (z.read("export_poly.bin")
                         if "export_poly.bin" in z.namelist()
                         else None)
        if meta.get("version", 0) > _ARTIFACT_VERSION:
            raise ValueError(
                f"artifact version {meta.get('version')} is newer "
                f"than this runtime's {_ARTIFACT_VERSION}")
        in_tree = jax.tree_util.tree_structure(
            _tree_from_spec(meta["in_spec"]))
        out_tree = jax.tree_util.tree_structure(
            _tree_from_spec(meta["out_spec"]))
        n_dev = int(meta.get("n_devices", 1))
        trace_fn = None
        try:
            # execution_devices defaults to ALL of the backend's
            # devices — a single-device artifact must load onto
            # exactly the device count it was compiled for
            fn = se.deserialize_and_load(
                exec_blob, in_tree, out_tree,
                execution_devices=jax.devices()[:n_dev])
            mode = "aot"
        except Exception as e:
            backend = jax.default_backend()
            plats = meta.get("export_platforms", [meta["platform"]])
            if backend not in plats:
                raise ValueError(
                    f"artifact was exported for platform(s) {plats}; "
                    f"this process runs {backend} — re-export on a "
                    f"matching backend") from e
            logger.warning(
                "serialized executable not loadable here (%s: %s); "
                "compiling the portable export blob once",
                type(e).__name__, e)
            from jax import export as jexport
            exp = jexport.deserialize(export_blob)
            args = [jax.ShapeDtypeStruct(tuple(i["shape"]),
                                         np.dtype(i["dtype"]))
                    for i in meta["inputs"]]
            fn = jax.jit(exp.call).lower(*args).compile()
            mode = "export"
        if poly_blob is not None:
            # the batch-polymorphic program re-specializes for other
            # batch sizes — DynamicBatcher's bucket warming path
            try:
                from jax import export as jexport
                trace_fn = jax.jit(
                    jexport.deserialize(poly_blob).call)
            except Exception as e:
                logger.warning(
                    "polymorphic export blob unusable here (%s: %s);"
                    " serving the declared batch size only",
                    type(e).__name__, e)
        self.quantized = None     # any prior int8 load is replaced
        # export_src None: re-export needs a source model
        specs = [(tuple(i["shape"]), np.dtype(i["dtype"]))
                 for i in meta["inputs"]]
        self._swap_model(fn, compiled=True, export_src=None,
                         trace_fn=trace_fn, example_specs=specs)
        logger.info("loaded compiled serving artifact %s (mode=%s)",
                    path, mode)
        return self

    # -- predict ------------------------------------------------------------
    def predict(self, inputs, timeout_ms: int = -1):
        """Take a slot from the pool, run, return the slot (reference
        `doPredict` contract)."""
        # consistent snapshot (fn, conversion flag, queue): a reload
        # mid-predict must not mix generations (see _swap_model)
        with self._lock:
            predict_fn = self._predict_fn
            compiled = self._compiled
            queue = self._queue
        if predict_fn is None:
            raise RuntimeError("no model loaded")
        slot = queue.take(timeout_ms)
        if slot < 0:
            obs.counter("zoo_tpu_serving_errors_total",
                        help="serving errors by kind",
                        labels={"kind": "slot_timeout"}).inc()
            raise TimeoutError(
                f"no free model slot within {timeout_ms}ms "
                f"(concurrency={self.supported_concurrent_num})")
        try:
            xs = (inputs if isinstance(inputs, (list, tuple))
                  else [inputs])
            # device-resident inputs pass straight to a jit fn —
            # np.asarray would round-trip them through the host. The
            # AOT path (example_inputs) keeps the conversion: its
            # executable pins the example arrays' layout, which a
            # committed/sharded caller array need not match.
            xs = [x if isinstance(x, jax.Array)
                  and not compiled else np.asarray(x)
                  for x in xs]
            bdim = np.shape(xs[0])
            obs.histogram("zoo_tpu_serving_batch_size",
                          help="predict batch size (leading dim)",
                          buckets=obs.SIZE_BUCKETS).observe(
                bdim[0] if bdim else 1)
            with obs.span("serving/predict"):
                out = predict_fn(*xs)
                if isinstance(out, (list, tuple)):
                    return [np.asarray(o) for o in out]
                return np.asarray(out)
        finally:
            queue.put(slot)

    # -- generation (pipeline/inference/generation.py) ----------------------
    def load_generator(self, net, params=None, **engine_kwargs):
        """Attach an autoregressive decode engine for ``net`` (a
        decoder exposing ``init_kv_cache / prefill / decode_step /
        generate``: `layers.TransformerLayer`, or a
        `layers.PatternDecoder` such as `deepseek_v2_decoder` builds).
        Orthogonal to the ``load_*`` predict path:
        a model can serve ``/predict`` and ``/generate`` at once, and
        loading a generator does not invalidate warmed predict
        buckets. ``engine_kwargs`` forward to
        :class:`~analytics_zoo_tpu.pipeline.inference.generation.
        GenerationEngine` (``max_slots``, ``max_context``,
        ``page_size``, ``top_k``, ``cache_dtype``,
        ``prefill_chunk``, ``spec_k`` — env-defaulted,
        docs/perf_flags.md). For speculative decoding pass
        ``drafter=`` (a smaller net sharing the vocabulary);
        ``drafter_params`` defaults to the drafter's own estimator
        params the same way ``params`` defaults to ``net``'s."""
        from analytics_zoo_tpu.pipeline.inference.generation import \
            GenerationEngine

        def _params_of(n, explicit):
            if explicit is not None:
                return explicit
            est = n.estimator
            if est.params is None:
                est._ensure_initialized()
            return est.params

        params = _params_of(net, params)
        drafter = engine_kwargs.get("drafter")
        if drafter is not None:
            engine_kwargs["drafter_params"] = _params_of(
                drafter, engine_kwargs.get("drafter_params"))
        self._generator = GenerationEngine(net, params,
                                           **engine_kwargs)
        return self

    @property
    def generator(self):
        """The attached GenerationEngine, or None — how the serving
        front-ends decide whether to mount ``/generate``."""
        return self._generator

    def generate(self, prompts, max_new_tokens: int = 32, *,
                 temperature: float = 0.0, eos_id=None):
        """Sequential per-request generation: one compiled whole-loop
        program per (batch, prompt-bucket, budget) shape — the
        baseline the continuous batcher is benchmarked against
        (`scripts/bench_generate.py`). ``prompts``: one token-id list
        or a list of them. Returns a list of 1-D arrays of newly
        generated ids."""
        if self._generator is None:
            raise RuntimeError(
                "no generator loaded; call load_generator(net) first")
        return self._generator.generate(
            prompts, max_new_tokens=max_new_tokens,
            temperature=temperature, eos_id=eos_id)

    # -- dynamic-batching hooks (pipeline/inference/batching.py) ------------
    @property
    def generation(self) -> int:
        """Bumped on every model (re)load — lets DynamicBatcher
        invalidate its per-bucket executable cache on reload."""
        return self._generation

    @property
    def can_relower(self) -> bool:
        """Whether the loaded model keeps a traceable form that can
        be AOT-lowered for NEW input shapes (bucket warming). False
        only for ``load_compiled`` artifacts without a
        batch-polymorphic export blob."""
        return self._trace_fn is not None

    @property
    def example_input_specs(self):
        """``[(shape, np.dtype), ...]`` of the declared example
        inputs (load-time ``example_inputs`` or a compiled artifact's
        manifest), or ``None`` when the model was loaded without
        shape declarations."""
        with self._lock:
            specs = self._example_specs
        return None if specs is None else list(specs)

    def lower_for(self, example_args: Sequence):
        """AOT-lower-and-compile the loaded forward for exactly the
        given arguments (arrays or ``jax.ShapeDtypeStruct``) and
        return the compiled executable — the primitive DynamicBatcher
        uses to warm its bucket ladder. The executable is NOT
        installed; :meth:`predict` is unaffected."""
        with self._lock:
            fn = self._trace_fn
        if fn is None:
            raise RuntimeError(
                "model cannot be re-lowered for new shapes (a "
                "load_compiled artifact without a batch-polymorphic "
                "export blob, or no model loaded)")
        return fn.lower(*example_args).compile()

    @property
    def concurrent_slots_free(self) -> int:
        return self._queue.size()

    def __repr__(self):
        return (f"InferenceModel(concurrency="
                f"{self.supported_concurrent_num}, "
                f"loaded={self._predict_fn is not None}, "
                f"aot={self._compiled})")
