#!/usr/bin/env python
"""ResNet-50 ImageNet training — BASELINE workload #2.

Mirrors the reference config (pyzoo/zoo/examples/orca/learn/tf2/resnet/
resnet-50-imagenet.py:26-33,351,382-386): 256 images/batch/worker, peak LR
0.1 x global_batch/256 with 5-epoch warmup then poly decay.

With --data-dir pointing at raw-uint8 shard files (see
orca/data/image/imagenet.py for the on-disk format and a converter from
JPEG directories), trains on real data; otherwise writes a synthetic shard
set so the script runs anywhere.

Usage:
    python examples/orca/learn/resnet50_imagenet.py --smoke
    python examples/orca/learn/resnet50_imagenet.py --data-dir /data/imagenet
"""

import argparse
import shutil
import tempfile


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--data-dir", default=None,
                   help="imagenet shard dir (synthetic data if omitted)")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--depth", type=int, default=50,
                   choices=(18, 34, 50, 101, 152))
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes, a few steps (CI)")
    args = p.parse_args()

    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.models.image.resnet import resnet
    from analytics_zoo_tpu.orca.data.image import (ImageNetPipeline,
                                                   write_synthetic_imagenet)
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.optimizers import SGD
    from analytics_zoo_tpu.orca.learn.optimizers.schedule import (
        Poly, SequentialSchedule, Warmup)

    ctx = init_orca_context("local")
    if args.smoke:
        args.batch, args.depth, crop, image_size, num_images = 32, 18, 64, 72, 128
    else:
        crop, image_size, num_images = 224, 232, 2048

    data_dir, tmp = args.data_dir, None
    if data_dir is None:
        tmp = data_dir = tempfile.mkdtemp(prefix="zoo_example_imagenet_")
        write_synthetic_imagenet(data_dir, num_images=num_images,
                                 image_size=image_size, shard_size=1024)
    try:
        pipe = ImageNetPipeline(data_dir, batch_size=args.batch,
                                mesh=ctx.mesh, crop_size=crop, train=True)
        peak = 0.1 * pipe.global_bs / 256
        warm = max(5 * pipe.steps_per_epoch, 1)
        sched = (SequentialSchedule()
                 .add(Warmup(delta=peak / warm), warm)
                 .add(Poly(2.0, 85 * pipe.steps_per_epoch),
                      85 * pipe.steps_per_epoch))
        est = TPUEstimator(
            resnet(depth=args.depth, num_classes=1000),
            loss="sparse_categorical_crossentropy",
            optimizer=SGD(learningrate=0.0, momentum=0.9,
                          leaningrate_schedule=sched))

        # the production path (what chip_smoke.py proves on the chip): fit
        # drives the pipeline through the InfeedPump and its transfer lanes
        for stats in est.fit(pipe, epochs=args.epochs, verbose=False):
            print(f"epoch {stats['epoch']}: "
                  f"train_loss={stats['train_loss']:.4f} "
                  f"({pipe.steps_per_epoch} steps, "
                  f"global batch {pipe.global_bs}, {stats['time_s']} s)")
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
        stop_orca_context()


if __name__ == "__main__":
    main()
