from analytics_zoo_tpu.models.image.imageclassification.image_classifier \
    import ImageClassifier
from analytics_zoo_tpu.models.image.imageclassification.resnet import (
    resnet50, ResNet)
from analytics_zoo_tpu.models.image.imageclassification.lenet import lenet5
from analytics_zoo_tpu.models.image.imageclassification.archs import (
    vgg16, vgg19, inception_v1, mobilenet, mobilenet_v2, densenet121,
    squeezenet)

__all__ = ["ImageClassifier", "resnet50", "ResNet", "lenet5",
           "vgg16", "vgg19", "inception_v1", "mobilenet", "mobilenet_v2",
           "densenet121", "squeezenet"]
