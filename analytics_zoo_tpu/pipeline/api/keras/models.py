"""Containers: `Sequential` and functional `Model` (+ shared `KerasNet`).

Analog of reference `Z/pipeline/api/keras/models/Topology.scala:572-889`
(`Model` graph / `Sequential`). Training methods (`compile/fit/...`) are
attached in `topology.py`; this module is the structural half: parameter
init with Keras-style shape-inference chaining, pure forward, summary.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.pipeline.api.keras.engine import (
    KerasLayer, ShapeLike, Variable, _InputLayer,
    collect_layers, topological_order, unique_name,
)


class KerasNet(KerasLayer):
    """Shared container behavior. Containers are layers, so they nest."""

    def __init__(self, name: Optional[str] = None):
        super().__init__(name=name)

    def _canonicalize_names(self, layers: "list[KerasLayer]") -> None:
        """Rename auto-named layers to container-scoped deterministic
        names (`dense_1`, `dense_2`, ... in container order).

        Auto-generated names are process-global counters, so two builds
        of the same architecture get different names; params dicts are
        keyed by name, so checkpoints/save_model would not transfer.
        Scoping the numbering to the container makes names a pure
        function of the architecture. User-provided names are kept.
        Note: a shared layer re-used across two separately-built models
        is renamed by whichever container canonicalizes it last.
        """
        counters: "dict[str, int]" = {}
        for lyr in layers:
            prefix = type(lyr).__name__.lower()
            counters[prefix] = counters.get(prefix, 0) + 1
            if getattr(lyr, "_auto_named", False):
                lyr.name = f"{prefix}_{counters[prefix]}"

    # -- to be provided by subclasses ---------------------------------------
    @property
    def layers(self) -> "list[KerasLayer]":
        raise NotImplementedError

    # -- params -------------------------------------------------------------
    def init_params(self, rng=None,
                    input_shape: Optional[ShapeLike] = None,
                    device=None) -> dict:
        """Build the whole parameter pytree.

        ``rng`` defaults to a key from the process NNContext so plain
        ``model.init_params()`` "just works" after ``init_nncontext()``.

        Init is ~hundreds of tiny eager ops (one per leaf); against a
        remote accelerator each would pay a dispatch round trip, so on
        non-CPU backends the ops run on the host CPU backend and the
        finished pytree transfers in ONE ``device_put`` (the
        remote-TPU analog of the reference's driver-side weight init +
        broadcast). ``device``: a placement target, or ``"host"`` to
        skip the transfer and return the CPU-resident pytree (callers
        that re-place with their own shardings — Estimator — avoid a
        full-replica round trip through device 0 that way).
        """
        import jax

        if rng is None:
            from analytics_zoo_tpu.common.nncontext import get_nncontext
            rng = get_nncontext().next_rng_key()
        try:
            cpu0 = jax.local_devices(backend="cpu")[0]
        except RuntimeError:  # no host backend under a platform pin
            cpu0 = None
        if cpu0 is None or (device is None
                            and jax.default_backend() == "cpu"):
            return self.init(rng, input_shape)
        with jax.default_device(cpu0):
            params = self.init(jax.device_put(rng, cpu0), input_shape)
        if device == "host":
            return params
        return jax.device_put(
            params, device if device is not None else jax.devices()[0])

    def forward(self, params: dict, inputs, *, training: bool = False,
                rng=None):
        out, _ = self.apply(params, inputs, training=training, rng=rng)
        return out

    def regularization_loss(self, params: dict):
        loss = jnp.zeros((), jnp.float32)
        for lyr in self.layers:
            sub = params.get(lyr.name, {})
            loss = loss + lyr.regularization_loss(sub)
        return loss

    def trainable_mask(self, params: dict) -> dict:
        """Bool pytree: True where the optimizer should update.

        ``_state`` subtrees (BatchNorm stats) and layers frozen via
        ``trainable=False`` are masked out (reference analog: `freezeUpTo`,
        `NetUtils.scala:47-140`).
        """
        def mask_layer(lyr: KerasLayer, sub: dict) -> Any:
            if isinstance(lyr, KerasNet):
                return {inner.name: mask_layer(inner,
                                               sub.get(inner.name, {}))
                        for inner in lyr.layers if inner.name in sub}
            def mask_sub(node):
                # "_state" subtrees are non-trainable at ANY nesting
                # depth (a composite layer may keep per-BN state
                # under params["bn1"]["_state"], ...)
                if isinstance(node, dict):
                    return {k: (jax.tree_util.tree_map(
                                    lambda _: False, v)
                                if k == "_state" else mask_sub(v))
                            for k, v in node.items()}
                return jax.tree_util.tree_map(
                    lambda _: bool(lyr.trainable), node)
            out = mask_sub(sub)
            return out
        return {lyr.name: mask_layer(lyr, params.get(lyr.name, {}))
                for lyr in self.layers if lyr.name in params}

    def freeze(self, *layer_names: str) -> "KerasNet":
        """Freeze named layers (all layers if no names given)."""
        targets = set(layer_names)
        for lyr in self.layers:
            if not targets or lyr.name in targets:
                lyr.trainable = False
        return self

    def unfreeze(self, *layer_names: str) -> "KerasNet":
        targets = set(layer_names)
        for lyr in self.layers:
            if not targets or lyr.name in targets:
                lyr.trainable = True
        return self

    # -- training surface (reference `Topology.scala:128-540`:
    #    compile/fit/evaluate/predict + tensorboard/checkpoint/clipping) ----
    def compile(self, optimizer="adam", loss="mse", metrics=None):
        """Configure training (reference `KerasNet.compile`,
        `Topology.scala:128-184`; accepts string names, optimizer objects,
        loss callables incl. `autograd.CustomLoss`). Re-compiling keeps
        already-initialized weights (keras semantics — imported/trained
        params survive an optimizer/loss change)."""
        from analytics_zoo_tpu.pipeline.estimator import (
            Estimator,
            _check_params_compatible,
        )
        old = getattr(self, "_estimator", None)
        self._estimator = Estimator(self, optimizer=optimizer, loss=loss,
                                    metrics=metrics)
        if old is not None and old.params is not None:
            try:
                _check_params_compatible(self, old.params)
                self._estimator.params = old.params
            except (KeyError, ValueError):
                # topology changed since the old compile — re-init
                from analytics_zoo_tpu.common.nncontext import logger
                logger.warning(
                    "compile: existing params no longer match the "
                    "model topology; weights will be re-initialized")
        return self

    @property
    def estimator(self):
        est = getattr(self, "_estimator", None)
        if est is None:
            raise RuntimeError("call compile(...) first")
        return est

    def set_tensorboard(self, log_dir: str, app_name: str = "zoo_tpu"):
        """(reference `Topology.scala:197`)"""
        self.estimator.set_tensorboard(log_dir, app_name)
        return self

    def set_summary_trigger(self, name: str, trigger):
        """Extra TB summaries on a trigger — "Parameters" writes
        per-layer weight histograms (BigDL
        `TrainSummary.setSummaryTrigger`)."""
        self.estimator.set_summary_trigger(name, trigger)
        return self

    def set_checkpoint(self, path: str, trigger=None):
        """(reference `Topology.scala:238-248`)"""
        self.estimator.set_checkpoint(path, trigger)
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float):
        """(reference `Topology.scala:254-284`)"""
        self.estimator.set_gradient_clipping_by_l2_norm(clip_norm)
        return self

    def set_constant_gradient_clipping(self, min_value, max_value):
        self.estimator.set_constant_gradient_clipping(min_value, max_value)
        return self

    def fit(self, x, y=None, batch_size: int = 32, nb_epoch: int = 10,
            validation_data=None, **kwargs):
        """Train (reference `KerasNet.fit`, `Topology.scala:336-481`).

        `x` may be numpy array(s) (+ `y`), an `ArrayDataset`, or any
        object with the FeatureSet protocol (`num_samples` +
        `iter_batches`)."""
        return self.estimator.train(
            x, y, batch_size=batch_size, nb_epoch=nb_epoch,
            validation_data=validation_data, **kwargs)

    def evaluate(self, x, y=None, batch_size: int = 32):
        """(reference `Topology.scala:489-540`)"""
        return self.estimator.evaluate(x, y, batch_size=batch_size)

    def predict(self, x, batch_size: int = 32, distributed: bool = True):
        """(reference `Predictable`, `pipeline/api/Predictor.scala:203`;
        `distributed` kept for API parity — execution is always sharded
        over the mesh)."""
        del distributed
        return self.estimator.predict(x, batch_size=batch_size)

    def predict_classes(self, x, batch_size: int = 32,
                        zero_based_label: bool = True):
        probs = self.predict(x, batch_size=batch_size)
        classes = np.argmax(probs, axis=-1)
        return classes if zero_based_label else classes + 1

    # -- persistence (reference `Topology.scala:754-775` saveModel /
    #    Net.load; weights-only analog of BigDL checkpoint files) ----------
    def save_weights(self, path: str):
        params = self.estimator.params if getattr(
            self, "_estimator", None) is not None and \
            self.estimator.params is not None else None
        if params is None:
            raise RuntimeError("no parameters to save; fit or init first")
        flat = {}
        for kp, leaf in jax.tree_util.tree_leaves_with_path(params):
            key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                           for p in kp)
            flat[key] = np.asarray(leaf)
        np.savez(path, **flat)

    def load_weights(self, path: str):
        import jax.tree_util as jtu
        data = np.load(path)
        est = self.estimator
        if est.params is None:
            est._ensure_initialized()
        leaves_with_path = jtu.tree_leaves_with_path(est.params)
        new_leaves = []
        for kp, leaf in leaves_with_path:
            key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                           for p in kp)
            if key not in data:
                raise KeyError(f"weight {key} missing from {path}")
            saved = data[key]
            if tuple(saved.shape) != tuple(leaf.shape):
                raise ValueError(
                    f"shape mismatch for {key}: saved {saved.shape} vs "
                    f"model {leaf.shape}")
            new_leaves.append(saved)
        treedef = jtu.tree_structure(est.params)
        est.params = jax.device_put(
            jtu.tree_unflatten(treedef, new_leaves))
        est._train_step = None
        return self

    def get_weights(self) -> "list[np.ndarray]":
        """Flat list of weight arrays in deterministic (sorted-path)
        order — the reference's `getWeights` (`Topology.scala`/
        `KerasNet.get_weights`). Pair with :meth:`set_weights`."""
        est = self.estimator
        if est.params is None:
            est._ensure_initialized()
        return [np.asarray(leaf)
                for _, leaf in jax.tree_util.tree_leaves_with_path(
                    est.params)]

    def copy_weights_from(self, other: "KerasNet",
                          strict: bool = False) -> "KerasNet":
        """Copy weights from another net BY LAYER NAME (the
        transfer-learning carry-over of the reference's
        `NetUtils.scala:47-140` surgery): layers present in both nets
        take `other`'s weights, the rest keep their own.
        ``strict=True`` requires every layer of this net to match."""
        src_est, dst_est = other.estimator, self.estimator
        if src_est.params is None:
            src_est._ensure_initialized()
        if dst_est.params is None:
            dst_est._ensure_initialized()
        src = src_est.params
        missing = [n for n in dst_est.params if n not in src]
        if strict and missing:
            raise KeyError(f"layers missing from source: {missing}")

        from analytics_zoo_tpu.common.nncontext import logger

        def _shapes(tree):
            return [(p, tuple(leaf.shape)) for p, leaf in
                    jax.tree_util.tree_leaves_with_path(tree)]

        new_params = {}
        for name, sub in dst_est.params.items():
            if name not in src:
                new_params[name] = sub
                continue
            if _shapes(src[name]) != _shapes(sub):
                if strict:
                    raise ValueError(
                        f"layer {name!r}: source weights "
                        f"{_shapes(src[name])} incompatible with "
                        f"{_shapes(sub)}")
                logger.warning(
                    "copy_weights_from: skipping layer %r — source "
                    "shapes %s != destination %s", name,
                    _shapes(src[name]), _shapes(sub))
                new_params[name] = sub
                continue
            # dtype differences (e.g. f32 backbone -> bf16 model) cast
            # to the destination's dtype rather than skipping
            new_params[name] = jax.tree_util.tree_map(
                lambda s, d: jnp.asarray(s, d.dtype), src[name], sub)
        dst_est.params = new_params
        dst_est._train_step = None           # invalidate compiled step
        return self

    def set_weights(self, weights: "list[np.ndarray]"):
        """Inverse of :meth:`get_weights` (shape-checked)."""
        import jax.tree_util as jtu
        est = self.estimator
        if est.params is None:
            est._ensure_initialized()
        leaves = jtu.tree_leaves(est.params)
        if len(weights) != len(leaves):
            raise ValueError(
                f"expected {len(leaves)} arrays, got {len(weights)}")
        new = []
        for cur, w in zip(leaves, weights):
            w = np.asarray(w)
            if tuple(w.shape) != tuple(cur.shape):
                raise ValueError(
                    f"shape mismatch: model {cur.shape} vs {w.shape}")
            new.append(w.astype(cur.dtype))
        est.params = jax.device_put(jtu.tree_unflatten(
            jtu.tree_structure(est.params), new))
        est._train_step = None
        return self

    # -- introspection ------------------------------------------------------
    def summary(self, params: Optional[dict] = None,
                line_length: int = 76) -> str:
        """Printable per-layer summary (reference `Topology.scala:567`)."""
        rows = [("Layer (type)", "Output Shape", "Param #")]
        total = 0
        for lyr in self.layers:
            n = (lyr.param_count(params.get(lyr.name, {}))
                 if params else 0)
            total += n
            rows.append((f"{lyr.name} ({type(lyr).__name__})",
                         str(lyr.output_shape), str(n) if params else "?"))
        widths = [max(len(r[i]) for r in rows) + 2 for i in range(3)]
        lines = ["=" * line_length]
        for i, r in enumerate(rows):
            lines.append("".join(c.ljust(w) for c, w in zip(r, widths)))
            if i == 0:
                lines.append("-" * line_length)
        lines.append("=" * line_length)
        if params:
            lines.append(f"Total params: {total}")
        text = "\n".join(lines)
        print(text)
        return text


class Sequential(KerasNet):
    """Linear stack of layers (reference `Topology.scala:779-889`)."""

    def __init__(self, layers: Optional[Sequence[KerasLayer]] = None,
                 name: Optional[str] = None):
        super().__init__(name=name or unique_name("sequential"))
        self._layers: "list[KerasLayer]" = []
        for lyr in layers or []:
            self.add(lyr)

    @property
    def layers(self) -> "list[KerasLayer]":
        return self._layers

    def add(self, layer: KerasLayer) -> "Sequential":
        if not isinstance(layer, KerasLayer):
            raise TypeError(f"expected a KerasLayer, got {type(layer)}")
        if not self._layers and layer._given_input_shape is None and not \
                isinstance(layer, KerasNet):
            raise ValueError(
                "first layer of a Sequential needs input_shape=...")
        self._layers.append(layer)
        self._canonicalize_names(self._layers)
        return self

    def build(self, rng, input_shape: ShapeLike) -> dict:
        params = {}
        shape = input_shape
        keys = jax.random.split(rng, max(len(self._layers), 1))
        for key, lyr in zip(keys, self._layers):
            params[lyr.name] = lyr.init(key, shape)
            shape = lyr.output_shape
        return params

    def init(self, rng, input_shape: Optional[ShapeLike] = None) -> dict:
        if input_shape is None:
            if not self._layers:
                raise ValueError("empty Sequential")
            first = self._layers[0]
            input_shape = first._given_input_shape
            if input_shape is None and isinstance(first, KerasNet):
                # nested container knows its own input shape
                inner = first
                while isinstance(inner, Sequential) and inner._layers:
                    inner = inner._layers[0]
                input_shape = inner._given_input_shape
            if input_shape is None:
                raise ValueError(
                    "cannot infer input shape; give the first layer "
                    "input_shape=...")
        return super().init(rng, input_shape)

    def compute_output_shape(self, input_shape: ShapeLike) -> ShapeLike:
        shape = input_shape
        for lyr in self._layers:
            shape = lyr.compute_output_shape(shape)
        return shape

    def apply(self, params: dict, inputs, *, training: bool = False,
              rng=None):
        x = inputs
        updates: dict = {}
        for i, lyr in enumerate(self._layers):
            sub_rng = jax.random.fold_in(rng, i) if rng is not None else None
            x, upd = lyr.apply(params[lyr.name], x, training=training,
                               rng=sub_rng)
            if upd:
                updates[lyr.name] = upd
        return x, updates

    def call(self, params, inputs, *, training=False, rng=None):
        out, _ = self.apply(params, inputs, training=training, rng=rng)
        return out


class Model(KerasNet):
    """Functional graph model (reference `Topology.scala:572-658`).

    Built from `Input(...)` variables through layer calls; supports
    multi-input/multi-output and shared layers (a layer instance used at
    several nodes contributes one set of params).
    """

    def __init__(self, inputs: "Variable | Sequence[Variable]",
                 outputs: "Variable | Sequence[Variable]",
                 name: Optional[str] = None):
        super().__init__(name=name or unique_name("model"))
        self.inputs: "list[Variable]" = (
            list(inputs) if isinstance(inputs, (list, tuple)) else [inputs])
        self.outputs: "list[Variable]" = (
            list(outputs) if isinstance(outputs, (list, tuple))
            else [outputs])
        self._order = topological_order(self.outputs)
        for v in self.inputs:
            if v not in self._order:
                raise ValueError(f"input {v} is not connected to outputs")
        self._graph_layers = collect_layers(self._order)
        self._multi_out = isinstance(outputs, (list, tuple))
        # deterministic names: rename auto-named layers in graph order,
        # keeping node names in sync for new_graph/freeze_up_to lookups
        old_names = {id(lyr): lyr.name for lyr in self._graph_layers}
        self._canonicalize_names(self._graph_layers)
        for v in self._order:
            if v.layer is not None and \
                    v.name == old_names.get(id(v.layer)):
                v.name = v.layer.name

    @property
    def layers(self) -> "list[KerasLayer]":
        return self._graph_layers

    def build(self, rng, input_shape: ShapeLike) -> dict:
        del input_shape  # graph shapes come from the Input variables
        params = {}
        keys = jax.random.split(rng, max(len(self._graph_layers), 1))
        built = {}
        # walk nodes in order so every layer sees its node input shape
        for v in self._order:
            lyr = v.layer
            if lyr is None or isinstance(lyr, _InputLayer):
                continue
            if id(lyr) in built:
                continue
            if not v.parents:  # zero-input node (Parameter / Constant)
                in_shape: ShapeLike = v.shape
            else:
                in_shape = ([p.shape for p in v.parents]
                            if len(v.parents) > 1 else v.parents[0].shape)
            idx = len(built)
            params[lyr.name] = lyr.init(keys[idx], in_shape)
            built[id(lyr)] = True
        return params

    def init(self, rng, input_shape: Optional[ShapeLike] = None) -> dict:
        shape: ShapeLike = ([v.shape for v in self.inputs]
                            if len(self.inputs) > 1
                            else self.inputs[0].shape)
        return super().init(rng, input_shape or shape)

    def compute_output_shape(self, input_shape: ShapeLike) -> ShapeLike:
        shapes = [v.shape for v in self.outputs]
        return shapes if self._multi_out else shapes[0]

    def apply(self, params: dict, inputs, *, training: bool = False,
              rng=None):
        xs = list(inputs) if isinstance(inputs, (list, tuple)) else [inputs]
        if len(xs) != len(self.inputs):
            raise ValueError(
                f"model {self.name} expects {len(self.inputs)} inputs, "
                f"got {len(xs)}")
        values: "dict[int, Any]" = {id(v): x
                                    for v, x in zip(self.inputs, xs)}
        updates: dict = {}
        for i, v in enumerate(self._order):
            if id(v) in values:
                continue
            lyr = v.layer
            if lyr is None or isinstance(lyr, _InputLayer):
                raise ValueError(
                    f"graph input {v.name} was not fed; it must be listed "
                    "in Model(inputs=...)")
            args = [values[id(p)] for p in v.parents]
            arg = (None if not args
                   else args if len(args) > 1 else args[0])
            sub_rng = jax.random.fold_in(rng, i) if rng is not None else None
            out, upd = lyr.apply(params[lyr.name], arg, training=training,
                                 rng=sub_rng)
            if upd:
                # shared layers may emit updates at several nodes; last wins
                updates[lyr.name] = upd
            values[id(v)] = out
        outs = [values[id(v)] for v in self.outputs]
        return (outs if self._multi_out else outs[0]), updates

    def call(self, params, inputs, *, training=False, rng=None):
        out, _ = self.apply(params, inputs, training=training, rng=rng)
        return out

    def new_graph(self, output_names: "list[str]") -> "Model":
        """Sub-graph ending at the named variables (reference `GraphNet.
        newGraph`, `NetUtils.scala:47-140` — transfer-learning surgery)."""
        by_name = {v.name: v for v in self._order}
        missing = [n for n in output_names if n not in by_name]
        if missing:
            raise ValueError(f"no graph nodes named {missing}")
        outs = [by_name[n] for n in output_names]
        return Model(self.inputs, outs if len(outs) > 1 else outs[0])

    def freeze_up_to(self, *node_names: str) -> "Model":
        """Freeze every layer at or before the named nodes (reference
        `freezeUpTo`)."""
        by_name = {v.name: v for v in self._order}
        missing = [n for n in node_names if n not in by_name]
        if missing:
            raise ValueError(f"no graph nodes named {missing}")
        frontier = [by_name[n] for n in node_names]
        seen = set()
        while frontier:
            v = frontier.pop()
            if id(v) in seen:
                continue
            seen.add(id(v))
            if v.layer is not None and not isinstance(v.layer, _InputLayer):
                v.layer.trainable = False
            frontier.extend(v.parents)
        return self
