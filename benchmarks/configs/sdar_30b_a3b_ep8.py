"""Factory of ``sdar_30b_a3b_ep8``: the program's decoder model at the widths
of the configuration's file, as the rank that the file names holds it, trained
as a block-diffusion LM inside a ``TPUEstimator`` with the configuration's
AdamW recipe, and where each of the reference's parameters lives in the
program's tree."""


def model_config(cfg):
    """The configuration's file counts the experts HELD under the published
    key ``num_experts`` (the cut; the published count is beside it): the
    model is told the router's full width and which experts it holds.
    ``n_routed_experts`` repeats the router's width under the name the
    token driver's phases read. Every layer's attention is global, as
    ``layer_types`` says for the grouped-query family's work module
    (``work_gqa.expert_min_seconds`` counts this family's experts too)."""
    held = int(cfg["num_experts"])
    return dict(cfg, num_experts=int(cfg["num_experts_published"]),
                n_routed_experts=int(cfg["num_experts_published"]),
                experts_held=held,
                first_expert=int(cfg["expert_parallel_rank"]) * held,
                layer_types=["full_attention"]
                * int(cfg["num_hidden_layers"]))


def noise_seed(seed):
    """The noise's seed: the estimator's own, so a run's noise follows from
    its ``--seed`` and the step."""
    return seed % (2 ** 31 - 1)


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
    module = DecoderLM.from_config(dict(model_config(cfg),
                                        noise_seed=noise_seed(seed)))
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
        mesh=mesh, seed=noise_seed(seed))
    est.set_l2_norm_gradient_clipping(opt["clip_norm"])
    return est


def program_path(cfg, name):
    """'layers_1/self_attn/q_proj/kernel' -> the same, as a tuple: the
    reference names its leaves by the program's tree paths."""
    del cfg
    return tuple(name.split("/"))
