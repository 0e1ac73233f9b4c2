"""Factory of ``inception_v1_imagenet``: the program's ``InceptionV1`` inside
a ``TPUEstimator`` with the configuration's recipe, and where each of the
reference's parameters lives in the program's tree."""

from harness.fit_cell import sgd_estimator


def build(cfg, mesh, global_batch, steps_per_epoch, seed):
    import jax.numpy as jnp
    from analytics_zoo_tpu.models.image.imageclassification.inception import \
        InceptionV1
    module = InceptionV1(num_classes=cfg["num_classes"],
                         compute_dtype=jnp.dtype(cfg["compute_dtype"]),
                         dropout=cfg["dropout"])
    return sgd_estimator(module, cfg, mesh, global_batch, steps_per_epoch,
                         seed)


def program_path(cfg, name):
    """'inception_3a/b2_reduce/conv/kernel' ->
    ('inception_3a', 'b2_reduce_conv', 'kernel'); 'stem/bn/scale' ->
    ('stem_bn', 'scale')."""
    parts = name.split("/")
    if parts[0] == "head":
        return ("head", parts[1])
    if parts[0].startswith("inception_"):
        return (parts[0], f"{parts[1]}_{parts[2]}", parts[3])
    return (f"{parts[0]}_{parts[1]}", parts[2])
