"""Factory of ``nemotron3_super_tp8_ep64``: the program's decoder model at the
widths of the configuration's file, as the rank that the file names holds it,
inside a ``TPUEstimator`` with the configuration's AdamW recipe, and where
each of the reference's parameters lives in the program's tree."""

# the published keys under which the file counts what is HELD here (the cut;
# each published count is beside it as ``<key>_published``)
_HEADS = ("mamba_num_heads", "n_groups", "num_attention_heads",
          "num_key_value_heads")


def model_config(cfg):
    """The model's view: the layers' published head counts, of which
    ``mixer_parallel_size`` / ``mixer_parallel_rank`` give this rank's share;
    the router's full width and which experts are held; the letters of the
    layers it has, from ``first_published_layer`` on
    (``hybrid_override_pattern`` is kept whole in the file)."""
    held = int(cfg["n_routed_experts"])
    first = int(cfg.get("first_published_layer", 0))
    layers = int(cfg["num_hidden_layers"])
    return dict(
        cfg, **{k: int(cfg[f"{k}_published"]) for k in _HEADS},
        n_routed_experts=int(cfg["n_routed_experts_published"]),
        experts_held=held,
        first_expert=int(cfg["expert_parallel_rank"]) * held,
        hybrid_override_pattern=cfg["hybrid_override_pattern"][
            first:first + layers])


def build(cfg, mesh, global_batch, steps_per_epoch, seed):
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.optimizers import AdamWeightDecay
    from analytics_zoo_tpu.orca.learn.optimizers.schedule import (
        Default, SequentialSchedule, Warmup)
    from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import \
        DecoderLM
    del global_batch, steps_per_epoch
    opt = cfg["optimizer"]
    if opt["kind"] != "adamw":
        raise ValueError(f"this factory builds adamw, the configuration "
                         f"asks for {opt['kind']!r}")
    module = DecoderLM.from_config(model_config(cfg))
    warm = int(opt["warmup_steps"])
    step = opt["peak_lr"] / warm
    sched = (SequentialSchedule()
             .add(Warmup(delta=step), warm - 1)
             .add(Default(), 2 ** 31 - 1))
    est = TPUEstimator(
        module, loss=module.loss(),
        optimizer=AdamWeightDecay(lr=step, weight_decay=opt["weight_decay"],
                                  beta_1=opt["beta_1"], beta_2=opt["beta_2"],
                                  epsilon=opt["epsilon"], schedule=sched),
        mesh=mesh, seed=seed % (2 ** 31 - 1))
    est.set_l2_norm_gradient_clipping(opt["clip_norm"])
    return est


def program_path(cfg, name):
    """'layers_0/mixer/in_proj/kernel' -> the same, as a tuple: the
    reference names its leaves by the program's tree paths."""
    del cfg
    return tuple(name.split("/"))
