"""ImageClassifier (reference
`Z/models/image/imageclassification/ImageClassifier.scala:55` + config
registry): a ZooModel dispatching to named architectures."""

from __future__ import annotations

from typing import Tuple

from analytics_zoo_tpu.models.common import ZooModel


def _builders():
    """Single name→builder registry; ARCHS derives from its keys so the
    validation tuple and the dispatch can never drift."""
    from analytics_zoo_tpu.models.image.imageclassification import archs
    from analytics_zoo_tpu.models.image.imageclassification.lenet import \
        lenet5
    from analytics_zoo_tpu.models.image.imageclassification.resnet \
        import ResNet
    reg = {
        "lenet-5": lenet5,
        "vgg-16": archs.vgg16,
        "vgg-19": archs.vgg19,
        "inception-v1": archs.inception_v1,
        "mobilenet": archs.mobilenet,
        "mobilenet-v2": archs.mobilenet_v2,
        "densenet-121": archs.densenet121,
        "squeezenet": archs.squeezenet,
    }
    for d in (50, 101, 152):
        reg[f"resnet-{d}"] = ResNet(d).build
    return reg


class ImageClassifier(ZooModel):
    """``ImageClassifier(model_name="resnet-50")`` — named-architecture
    image classification (the pretrained-weight registry of the reference
    maps to `load_model` files here)."""

    class _ArchList:
        """Class-level descriptor so both ``ImageClassifier.ARCHS`` and
        ``instance.ARCHS`` yield the architecture-name tuple."""

        def __get__(self, obj, objtype=None):
            return tuple(_builders())

    ARCHS = _ArchList()

    def __init__(self, model_name: str = "resnet-50",
                 input_shape: Tuple[int, int, int] = (224, 224, 3),
                 classes: int = 1000):
        super().__init__()
        name = model_name.lower()
        if name not in _builders():
            raise ValueError(f"unknown architecture '{model_name}'; "
                             f"known: {tuple(_builders())}")
        self.model_name = name
        self.input_shape = tuple(input_shape)
        self.classes = int(classes)

    def hyper_parameters(self):
        return {"model_name": self.model_name,
                "input_shape": self.input_shape,
                "classes": self.classes}

    @classmethod
    def from_hyper_parameters(cls, hp: dict):
        """Configs saved before the fused conv+BN ResNet was deleted
        carry ``"fused"``: false loads as it always did; true names
        a parameter layout no builder makes any more, and is
        refused."""
        hp = dict(hp)
        if hp.pop("fused", False):
            raise ValueError(
                f"this {hp.get('model_name', 'ResNet')} config was "
                "saved with fused=True: the fused Pallas conv+BN "
                "bottlenecks were deleted (PR 32: 2.8x slower a step "
                "than the XLA graph on the v5e, PERF.md §6) and no "
                "builder makes their parameter layout any more")
        return cls(**hp)

    def build_model(self):
        return _builders()[self.model_name](self.input_shape,
                                            self.classes)

    @classmethod
    def load_model(cls, path_or_name: str, weights_path=None,
                   input_shape=(224, 224, 3), classes: int = 1000,
                   allow_random: bool = False):
        """Registry-aware load (reference
        `ImageClassifier.loadModel` by published name): a known
        architecture name (e.g. ``"resnet-50"``) builds it and loads
        shape-validated weights from ``weights_path`` /
        ``$ZOO_TPU_PRETRAINED_DIR`` (raising when no artifact is
        found unless ``allow_random=True``); anything else is a
        ``save_model`` file path."""
        from analytics_zoo_tpu.models.config import (
            ImageClassificationConfig, _resolve_weights,
            _strip_published_name)
        arch = _strip_published_name(path_or_name).lower()
        # registry route: known arch, OR an artifact for this published
        # name sits in $ZOO_TPU_PRETRAINED_DIR (e.g. a .model whose
        # arch has no built-in builder)
        if arch in _builders() or _resolve_weights(
                path_or_name, arch, None) is not None:
            return ImageClassificationConfig.create(
                path_or_name, input_shape=input_shape, classes=classes,
                weights_path=weights_path, allow_random=allow_random)
        return super().load_model(path_or_name)
