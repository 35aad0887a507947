"""ImageNet-style ResNet-50 training recipe — the reference's
Inception ImageNet example (`Z/examples/inception/Train.scala:70-107`:
SGD + warmup + poly decay, checkpoint every epoch) rebuilt TPU-first:

- data: an image folder via `ImageSet.read` (thread-pool decode) or
  synthetic data; light host resize only;
- augmentation ON DEVICE inside the jitted train step
  (`feature/image/device_transforms`): Inception-style
  random-resized crop, hflip, color jitter, normalize;
- model: `resnet50(space_to_depth=...)`, the space-to-depth stem
  for even crop sizes;
- training: Estimator over the mesh's ``data`` axis (bf16 activations
  on TPU by default), SGD momentum + warmup→poly schedule, epoch
  checkpoints (async write capable via ZOO_TPU_ASYNC_CKPT=1).

Demo sizes by default; scale --image-size/--batch-per-device/--epochs
for a real run. On CPU:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m analytics_zoo_tpu.examples resnet_imagenet --devices 8
"""

from __future__ import annotations

import argparse

import numpy as np


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--folder", default=None,
                   help="class_name/xxx.jpg image tree; synthetic "
                        "data when omitted")
    p.add_argument("--devices", type=int, default=0)
    p.add_argument("--image-size", type=int, default=64,
                   help="train crop size (224 for the real recipe)")
    p.add_argument("--batch-per-device", type=int, default=8)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--checkpoint", default=None)
    args = p.parse_args(argv)

    import jax

    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.feature.image import device_transforms as D
    from analytics_zoo_tpu.models.image.imageclassification.resnet \
        import resnet50
    from analytics_zoo_tpu.ops.optimizers import SGD, poly, warmup
    from analytics_zoo_tpu.pipeline.estimator import Estimator, \
        EveryEpoch

    n = args.devices or len(jax.devices())
    ctx = init_nncontext(tpu_mesh={"data": n},
                         devices=jax.devices()[:n], seed=0)
    s = args.image_size
    batch = args.batch_per_device * n

    # -- data ----------------------------------------------------------
    if args.folder:
        from analytics_zoo_tpu.feature.image import ImageSet
        from analytics_zoo_tpu.feature.image.transforms import \
            ImageResize
        iset = ImageSet.read(args.folder, with_label_from_dirs=True)
        # host side: decode + one resize to a fixed ingest size; all
        # randomized augmentation happens on device
        iset = iset.transform(ImageResize(int(s * 1.15),
                                          int(s * 1.15)))
        x, y = iset.to_arrays()   # stacked float32 NHWC + labels
        classes = int(y.max()) + 1
    else:
        rs = np.random.RandomState(0)
        n_samples = batch * 4
        x = rs.rand(n_samples, int(s * 1.15), int(s * 1.15), 3) \
            .astype(np.float32) * 255
        y = rs.randint(0, args.classes, size=(n_samples, 1))
        classes = args.classes

    if len(x) < batch:
        raise ValueError(
            f"{len(x)} samples < global batch {batch} "
            f"({args.batch_per_device} x {n} devices): every epoch "
            "would run zero steps")

    # -- on-device augmentation (train-only, inside the jitted step) ---
    aug = D.augment_pipeline(
        D.random_resized_crop((s, s), scale=(0.32, 1.0)),
        D.random_hflip(),
        D.random_brightness(32.0),
        D.random_saturation(0.3),
        D.normalize((123.68, 116.779, 103.939),
                    (58.393, 57.12, 57.375)))

    # -- model + recipe ------------------------------------------------
    model = resnet50(input_shape=(s, s, 3), classes=classes,
                     space_to_depth=(s % 2 == 0))
    steps_per_epoch = max(1, (len(x) // batch))
    total_steps = steps_per_epoch * args.epochs
    warm = max(1, total_steps // 20)
    # ramp lr/10 -> lr over `warm` steps, then poly decay from lr
    lr = warmup(args.lr / 10, warm, delta=(args.lr * 0.9) / warm,
                after=poly(args.lr, 0.5, max(1, total_steps - warm)))
    est = Estimator(model, optimizer=SGD(lr=lr, momentum=0.9),
                    loss="sparse_categorical_crossentropy",
                    metrics=["accuracy"], ctx=ctx, augment=aug)
    if args.checkpoint:
        est.set_checkpoint(args.checkpoint, trigger=EveryEpoch())

    res = est.train(x, y, batch_size=batch, nb_epoch=args.epochs)
    print(f"devices={n} crop={s} batch={batch} steps={est.step}")
    print(f"final epoch loss={res.history[-1]['loss']:.4f} "
          f"throughput={res.history[-1]['throughput']:.1f} img/s")
    return res.history


if __name__ == "__main__":
    main()
