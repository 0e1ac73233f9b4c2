"""FLOPs and least bytes that a training step of an ``sdar_moe``-family
decoder trained by block diffusion requires, counted from the configuration's
shapes alone: ``work_gqa.py``'s rules at this family's shapes and its doubled
sequence.

A sequence of L tokens passes every layer twice over, as ``[x_t | x_0]``: 2L
positions through the attention's projections and the routers; the head
scores the noisy half, L positions. One multiply-add is two FLOPs; training
a matrix of ``p`` parameters on ``t`` positions takes ``6 p t``. Attention
is counted at the block-diffusion mask's OWN score entries, ``L^2 + L B`` a
head (the noisy block's B keys and the clean blocks before it for a noisy
query, the clean blocks up to its own for a clean one); an entry costs ``2 (d
+ d)`` FLOPs forward (q.k and p.v) in each query head, its backward pass twice
that. Nothing recomputed is counted (not the rematerialised forward, not the
scores the flash backward rebuilds, not a walked tile's masked entries). The
experts held are counted at the rows really routed to them
(``moe_local_rows``, the program's counter), summed over the expert layers.

``cfg`` is the model's view of a configuration (the factory's
``model_config``): ``num_experts`` the router's width, ``experts_held`` this
rank's share. The held experts' least time is ``work_gqa.expert_min_seconds``
(the same count; the view names every layer ``full_attention`` for it), read
by ``gqa_expert_gmm_roofline``.
"""

from __future__ import annotations

from typing import Dict


def matrices(cfg: dict) -> Dict[str, int]:
    """Parameters of each kind of matrix, one copy."""
    h, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kv, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    return {
        # q and the output at the query heads' count, k and v at the kv's
        "attention": 2 * h * heads * d + 2 * h * kv * d,
        "router": h * int(cfg["num_experts"]),
        "expert": 3 * h * int(cfg["moe_intermediate_size"]),
        "head": h * int(cfg["vocab_size"]),
    }


def param_count(cfg: dict) -> int:
    """Parameters this rank holds: the layers' attention, router, held
    experts and two norms with the q and k norms, the embedding with the
    MASK token's row, the head, the final norm."""
    m, h = matrices(cfg), int(cfg["hidden_size"])
    held = int(cfg.get("experts_held", cfg["num_experts"]))
    layer = m["attention"] + m["router"] + held * m["expert"] \
        + 2 * h + 2 * int(cfg["head_dim"])
    return int(cfg["num_hidden_layers"]) * layer \
        + (int(cfg["vocab_size"]) + 1) * h + m["head"] + h


def layer_params_per_position(cfg: dict) -> int:
    """Matrix parameters every one of the 2L positions passes through in
    all layers (the held experts apart)."""
    m = matrices(cfg)
    return int(cfg["num_hidden_layers"]) * (m["attention"] + m["router"])


def score_entries(length: int, block: int) -> int:
    """Entries of one head's score matrix inside the block-diffusion mask
    over ``[x_t | x_0]`` of a sequence of ``length``: ``length * block`` in
    the noisy blocks, ``length^2 / 2 - length * block / 2`` from noisy to
    clean, ``length^2 / 2 + length * block / 2`` among the clean."""
    return length * length + length * block


def attention_flops_per_sequence(cfg: dict, length: int) -> Dict[str, float]:
    """One block's attention products on one sequence."""
    fwd = float(score_entries(length, int(cfg["block_length"]))) * 2 * 2 \
        * int(cfg["head_dim"]) * int(cfg["num_attention_heads"])
    return {"fwd": fwd, "bwd": 2 * fwd}


def train_flops_per_sample(cfg: dict, length: int,
                           local_rows_per_sample: float) -> float:
    """Forward and backward FLOPs one sequence of ``length`` tokens requires
    (2 * length positions through the layers); ``local_rows_per_sample``
    the token-choices its positions send to the experts held, summed over
    the expert layers."""
    a = attention_flops_per_sequence(cfg, length)
    return (6.0 * layer_params_per_position(cfg) * 2 * length
            + 6.0 * matrices(cfg)["head"] * length
            + 6.0 * matrices(cfg)["expert"] * local_rows_per_sample
            + int(cfg["num_hidden_layers"]) * (a["fwd"] + a["bwd"]))


def attention_min_seconds(cfg: dict, length: int, sequences: int,
                          dtype_bytes: int, peaks: Dict[str, float]) -> float:
    """Least time of a step's attention products in all blocks: per pass
    the larger of FLOPs over the peak rate and the bytes of q and the output
    (query heads), k and v (kv heads) at 2L positions over the HBM rate;
    their gradients too, backward."""
    heads, kv = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    att = attention_flops_per_sequence(cfg, length)
    io = 2 * length * 2 * (heads + kv) * int(cfg["head_dim"]) * dtype_bytes
    total = 0.0
    for flops, nbytes in ((att["fwd"], io), (att["bwd"], 2 * io)):
        total += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return total * sequences * int(cfg["num_hidden_layers"])


def dense_min_seconds(cfg: dict, length: int, sequences: int,
                      dtype_bytes: int, peaks: Dict[str, float]) -> float:
    """Least time of a step's dense products (the attention's four
    projections and the routers at 2L positions a sequence, the head at L;
    the held experts and the attention products apart): three passes
    (forward, input gradient, weight gradient) of ``2 p t`` FLOPs, each
    reading or writing every matrix once; the activations' bytes are left
    out, so it is a floor."""
    layers, head = layer_params_per_position(cfg), matrices(cfg)["head"]
    flops = 2.0 * (layers * 2 * length + head * length) * sequences
    one = max(flops / peaks["bf16_flops_per_s"],
              (layers + head) * dtype_bytes / peaks["hbm_bytes_per_s"])
    return 3 * one
