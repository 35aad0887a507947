"""Model-zoo base classes.

Reference: `Z/models/common/ZooModel.scala:39-154` (buildModel/saveModel/
predictClasses/summary) and `Ranker` (`models/common/Ranker.scala:33` —
NDCG@k and MAP evaluation over ranking datasets).
"""

from __future__ import annotations

import os
import pickle
from typing import Optional

import numpy as np

from analytics_zoo_tpu.pipeline.api.keras.models import KerasNet


class ZooModel:
    """Container for a built-in model: holds hyperparameters, builds the
    KerasNet lazily, and proxies the training surface."""

    def __init__(self):
        self._model: Optional[KerasNet] = None

    # -- to implement -------------------------------------------------------
    def build_model(self) -> KerasNet:
        raise NotImplementedError

    def hyper_parameters(self) -> dict:
        """Constructor kwargs needed to rebuild this model."""
        return {}

    @classmethod
    def from_hyper_parameters(cls, hp: dict) -> "ZooModel":
        """Rebuild from a saved config's ``hyper_parameters``; a
        model whose saved configs outlived a constructor argument
        checks them here."""
        return cls(**hp)

    # -- common surface -----------------------------------------------------
    @property
    def model(self) -> KerasNet:
        if self._model is None:
            self._model = self.build_model()
        return self._model

    def compile(self, optimizer="adam", loss="mse", metrics=None):
        self.model.compile(optimizer=optimizer, loss=loss, metrics=metrics)
        return self

    def fit(self, x, y=None, batch_size=32, nb_epoch=10, **kwargs):
        return self.model.fit(x, y, batch_size=batch_size,
                              nb_epoch=nb_epoch, **kwargs)

    def evaluate(self, x, y=None, batch_size=32):
        return self.model.evaluate(x, y, batch_size=batch_size)

    def predict(self, x, batch_size=32):
        return self.model.predict(x, batch_size=batch_size)

    def predict_classes(self, x, batch_size=32, zero_based_label=True):
        return self.model.predict_classes(
            x, batch_size=batch_size, zero_based_label=zero_based_label)

    def summary(self):
        params = None
        est = getattr(self.model, "_estimator", None)
        if est is not None:
            params = est.params
        return self.model.summary(params)

    # -- persistence (reference saveModel/loadModel) ------------------------
    def save_model(self, path: str, over_write: bool = False):
        """Save hyperparameters + weights; reload with
        ``<Class>.load_model(path)``."""
        if os.path.exists(path) and not over_write:
            raise FileExistsError(f"{path} exists; pass over_write=True")
        est = self.model.estimator
        if est.params is None:
            est._ensure_initialized()
        import jax
        state = {
            "class": type(self).__name__,
            "module": type(self).__module__,
            "hyper_parameters": self.hyper_parameters(),
            "params": jax.device_get(est.params),
        }
        with open(path, "wb") as f:
            pickle.dump(state, f)

    @classmethod
    def load_model(cls, path: str) -> "ZooModel":
        import importlib

        import jax

        from analytics_zoo_tpu.parallel.mesh import shard_params
        from analytics_zoo_tpu.common.nncontext import get_nncontext
        from analytics_zoo_tpu.pipeline.estimator import \
            _check_params_compatible
        from analytics_zoo_tpu.common.safe_pickle import checked_load
        state = checked_load(path)  # class-whitelist deserialization
        mod_name = str(state["module"])
        if mod_name != "analytics_zoo_tpu" and \
                not mod_name.startswith("analytics_zoo_tpu."):
            raise ValueError(
                f"saved model class {state['module']}.{state['class']} "
                "is not a framework model (tampered file?)")
        mod = importlib.import_module(state["module"])
        klass = getattr(mod, state["class"])
        if not (isinstance(klass, type) and issubclass(klass, ZooModel)):
            raise ValueError(
                f"{state['module']}.{state['class']} is not a ZooModel "
                "subclass (tampered file?)")
        inst = klass.from_hyper_parameters(state["hyper_parameters"])
        inst.compile()  # default compile; caller may re-compile
        est = inst.model.estimator
        _check_params_compatible(inst.model, state["params"])
        est.params = shard_params(state["params"], get_nncontext().mesh)
        return inst

    # -- weight files (the pretrained-registry storage format) --------------
    def save_weights(self, path: str):
        """Write weights as a flat ``.npz`` ("layer/param" keys) — the
        published-weights format of the pretrained registry
        (`models/config.py`; reference `ObjectDetectionConfig.scala:31`
        published `.model` URLs)."""
        est = self.model.estimator
        if est.params is None:
            est._ensure_initialized()
        import jax
        flat = {}

        def walk(prefix, d):
            for k, v in d.items():
                key = f"{prefix}/{k}" if prefix else str(k)
                if isinstance(v, dict):
                    walk(key, v)
                else:
                    flat[key] = np.asarray(v)

        walk("", jax.device_get(est.params))
        np.savez(path, **flat)

    def load_weights(self, path: str):
        """Load a ``save_weights`` ``.npz`` with per-tensor shape
        validation (reference `loadModel` weight checks)."""
        import jax

        from analytics_zoo_tpu.common.nncontext import get_nncontext
        from analytics_zoo_tpu.parallel.mesh import shard_params
        est = self.model.estimator
        if est.params is None:
            est._ensure_initialized()
        params = jax.device_get(est.params)
        with np.load(path) as data:
            saved = {k: data[k] for k in data.files}

        def walk(prefix, d):
            for k, v in list(d.items()):
                key = f"{prefix}/{k}" if prefix else str(k)
                if isinstance(v, dict):
                    walk(key, v)
                    continue
                if key not in saved:
                    raise KeyError(
                        f"weights file {path} is missing tensor "
                        f"{key!r} (wrong architecture?)")
                w = saved.pop(key)
                if tuple(w.shape) != tuple(np.shape(v)):
                    raise ValueError(
                        f"{key}: file shape {tuple(w.shape)} does not "
                        f"match model {tuple(np.shape(v))}")
                d[k] = w

        walk("", params)
        if saved:
            raise ValueError(
                f"weights file {path} has {len(saved)} unused tensors "
                f"(e.g. {sorted(saved)[:3]}) — wrong architecture?")
        est.params = shard_params(params, get_nncontext().mesh)
        # optimizer moments belong to the OLD weights — reset so the
        # next fit re-inits rather than resuming stale state
        est.opt_state = None
        est._train_step = None
        est._eval_step = None
        est._predict_fn = None
        return self


class ImportedZooModel(ZooModel):
    """ZooModel surface over a net imported from an external artifact
    (reference `ZooModel.loadModel`: the artifact defines the
    architecture). `build_model` re-imports from `artifact`, so
    ``save_model``/``load_model`` round-trips work as long as the
    artifact file stays in place (saved fine-tuned weights are
    shape-validated over the re-imported net)."""

    def __init__(self, artifact: str, model_name: str = "imported",
                 net: Optional[KerasNet] = None):
        super().__init__()
        self.artifact = str(artifact)
        self.model_name = str(model_name)
        self._model = net

    def build_model(self) -> KerasNet:
        from analytics_zoo_tpu.pipeline.api.net_load import Net
        return Net.load_bigdl(self.artifact)

    def hyper_parameters(self) -> dict:
        return {"artifact": self.artifact,
                "model_name": self.model_name}


class Ranker:
    """Ranking evaluation mixin (reference `models/common/Ranker.scala:33`):
    NDCG@k (`:112`) and MAP (`:147`) over grouped (query, candidates)
    relation lists."""

    @staticmethod
    def _group_scores(scores: np.ndarray, labels: np.ndarray,
                      group_ids: np.ndarray):
        order = np.argsort(group_ids, kind="stable")
        scores, labels, gids = scores[order], labels[order], group_ids[order]
        boundaries = np.flatnonzero(np.diff(gids)) + 1
        return (np.split(scores, boundaries), np.split(labels, boundaries))

    def evaluate_ndcg(self, scores, labels, group_ids, k: int = 3) -> float:
        """Mean NDCG@k over query groups."""
        s_groups, l_groups = self._group_scores(
            np.asarray(scores).reshape(-1), np.asarray(labels).reshape(-1),
            np.asarray(group_ids).reshape(-1))
        vals = []
        for s, l in zip(s_groups, l_groups):
            order = np.argsort(-s)[:k]
            gains = (2.0 ** l[order] - 1.0) / \
                np.log2(np.arange(2, len(order) + 2))
            ideal_order = np.argsort(-l)[:k]
            ideal = (2.0 ** l[ideal_order] - 1.0) / \
                np.log2(np.arange(2, len(ideal_order) + 2))
            denom = ideal.sum()
            if denom > 0:
                vals.append(gains.sum() / denom)
        return float(np.mean(vals)) if vals else 0.0

    def evaluate_map(self, scores, labels, group_ids) -> float:
        """Mean average precision over query groups."""
        s_groups, l_groups = self._group_scores(
            np.asarray(scores).reshape(-1), np.asarray(labels).reshape(-1),
            np.asarray(group_ids).reshape(-1))
        aps = []
        for s, l in zip(s_groups, l_groups):
            order = np.argsort(-s)
            rel = (l[order] > 0).astype(np.float64)
            if rel.sum() == 0:
                continue
            precision_at = np.cumsum(rel) / np.arange(1, len(rel) + 1)
            aps.append((precision_at * rel).sum() / rel.sum())
        return float(np.mean(aps)) if aps else 0.0
