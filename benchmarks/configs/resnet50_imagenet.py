"""Factory of ``resnet50_imagenet``: the program's ResNet at the widths and
depths of the configuration's file, inside a ``TPUEstimator`` with the
configuration's recipe, and where each of the reference's parameters lives in
the program's tree."""

from harness.fit_cell import sgd_estimator


def build(cfg, mesh, global_batch, steps_per_epoch, seed):
    import jax.numpy as jnp
    from analytics_zoo_tpu.models.image.resnet import BottleneckBlock, ResNet
    module = ResNet(stage_sizes=tuple(cfg["stage_sizes"]),
                    block_cls=BottleneckBlock,
                    num_classes=cfg["num_classes"],
                    num_filters=cfg["num_filters"],
                    compute_dtype=jnp.dtype(cfg["compute_dtype"]))
    return sgd_estimator(module, cfg, mesh, global_batch, steps_per_epoch,
                         seed)


_BLOCK = {"conv1": "Conv_0", "conv2": "Conv_1", "conv3": "Conv_2",
          "bn1": "BatchNorm_0", "bn2": "BatchNorm_1", "bn3": "BatchNorm_2",
          "proj": "proj_conv", "proj_bn": "proj_bn"}


def program_path(cfg, name):
    """'stage2/block0/conv1/kernel' -> ('BottleneckBlock_3','Conv_0','kernel');
    flax numbers the blocks through all stages."""
    parts = name.split("/")
    if parts[0] == "stem":
        return ("conv_init" if parts[1] == "conv" else "bn_init", parts[2])
    if parts[0] == "head":
        return ("head", parts[1])
    stage, block = int(parts[0][5:]) - 1, int(parts[1][5:])
    n = sum(cfg["stage_sizes"][:stage]) + block
    return (f"BottleneckBlock_{n}", _BLOCK[parts[2]], parts[3])
