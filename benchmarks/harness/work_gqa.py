"""FLOPs and least bytes that a training step of an ``afmoe``-family decoder
(gated grouped-query attention, sliding-window and global layers, a
sigmoid-routed expert layer) requires, counted from the configuration's
shapes alone: ``work_lm.py``'s rules at this family's shapes.

One multiply-add is two FLOPs; training a matrix of ``p`` parameters on ``t``
tokens takes ``6 p t``. Attention is counted at each layer kind's OWN score
entries: a global layer's query t sees ``t + 1`` keys, a sliding layer's
``min(t + 1, sliding_window)``; an entry costs ``2 (d + d)`` FLOPs forward (q.k
and p.v) in each query head, its backward pass twice that. Nothing
recomputed is counted (not the rematerialised forward, not the scores the
flash backward rebuilds, not the tiles' masked corners). The experts held are
counted at the rows really routed to them (``moe_local_rows``, the program's
counter), summed over the expert layers. q, the gate and the attention's
output move at the query heads' count, k and v at the kv heads'.

``cfg`` is the model's view of a configuration (the factory's
``model_config``): ``num_experts`` the router's width, ``experts_held`` this
rank's share, ``layer_types`` the kinds of the layers it has.
"""

from __future__ import annotations

from typing import Dict, Optional

KINDS = ("sliding_attention", "full_attention")


def blocks(cfg: dict) -> Dict[str, int]:
    """How many blocks of each kind the model applies."""
    layers = int(cfg["num_hidden_layers"])
    dense = int(cfg.get("num_dense_layers", 0))
    kinds = list(cfg["layer_types"])[:layers]
    return {"dense": dense, "expert": layers - dense, "all": layers,
            **{k: kinds.count(k) for k in KINDS}}


def matrices(cfg: dict) -> Dict[str, int]:
    """Parameters of each kind of matrix, one copy."""
    h, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kv, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    f = int(cfg["moe_intermediate_size"])
    return {
        # q, gate and output at the query heads' count, k and v at the kv's
        "attention": 3 * h * heads * d + 2 * h * kv * d,
        "dense_ffn": 3 * h * int(cfg["intermediate_size"]),
        "router": h * int(cfg["num_experts"]),
        "shared": 3 * h * f * int(cfg.get("num_shared_experts", 0)),
        "expert": 3 * h * f,
        "head": h * int(cfg["vocab_size"]),
    }


def norm_params(cfg: dict) -> int:
    """Four norms a block, the q and k norms over a head's width, the final
    norm."""
    h = int(cfg["hidden_size"])
    return blocks(cfg)["all"] * (4 * h + 2 * int(cfg["head_dim"])) + h


def param_count(cfg: dict) -> int:
    """Parameters this rank holds (the expert bias is state, not one)."""
    m, b = matrices(cfg), blocks(cfg)
    held = int(cfg.get("experts_held", cfg["num_experts"]))
    return (b["all"] * m["attention"] + b["dense"] * m["dense_ffn"]
            + b["expert"] * (m["router"] + m["shared"] + held * m["expert"])
            + 2 * m["head"] + norm_params(cfg))


def dense_params_per_token(cfg: dict) -> int:
    """Matrix parameters every token passes through (the held experts
    apart): the embedding is a lookup."""
    m, b = matrices(cfg), blocks(cfg)
    return (b["all"] * m["attention"] + b["dense"] * m["dense_ffn"]
            + b["expert"] * (m["router"] + m["shared"]) + m["head"])


def score_entries(seq: int, window: Optional[int]) -> int:
    """Entries of one head's score matrix inside the mask: the sum over
    query positions t of ``min(t + 1, window)``."""
    w = seq if window is None else min(int(window), seq)
    return w * (w + 1) // 2 + (seq - w) * w


def attention_flops_per_sequence(cfg: dict, seq: int, kind: str
                                 ) -> Dict[str, float]:
    """One block's attention products on one sequence, for a block of
    ``kind`` (an entry of ``layer_types``)."""
    window = int(cfg["sliding_window"]) if kind == "sliding_attention" \
        else None
    fwd = float(score_entries(seq, window)) * 2 * 2 * int(cfg["head_dim"]) \
        * int(cfg["num_attention_heads"])
    return {"fwd": fwd, "bwd": 2 * fwd}


def train_flops_per_sample(cfg: dict, seq: int,
                           local_rows_per_sample: float) -> float:
    """Forward and backward FLOPs one sequence of ``seq`` tokens requires;
    ``local_rows_per_sample`` the token-choices it sends to the experts held,
    summed over the expert layers."""
    b = blocks(cfg)
    att = 0.0
    for kind in KINDS:
        a = attention_flops_per_sequence(cfg, seq, kind)
        att += b[kind] * (a["fwd"] + a["bwd"])
    return (6.0 * dense_params_per_token(cfg) * seq
            + 6.0 * matrices(cfg)["expert"] * local_rows_per_sample + att)


def attention_min_seconds(cfg: dict, seq: int, sequences: int,
                          dtype_bytes: int, peaks: Dict[str, float],
                          kind: str) -> float:
    """Least time of a step's attention products in all blocks of ``kind``:
    per pass the larger of FLOPs over the peak rate and the bytes of q and
    the output (query heads), k and v (kv heads) over the HBM rate; their
    gradients too, backward."""
    heads, kv = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    att = attention_flops_per_sequence(cfg, seq, kind)
    io = seq * 2 * (heads + kv) * int(cfg["head_dim"]) * dtype_bytes
    total = 0.0
    for flops, nbytes in ((att["fwd"], io), (att["bwd"], 2 * io)):
        total += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return total * sequences * blocks(cfg)[kind]


def dense_min_seconds(cfg: dict, tokens: int, dtype_bytes: int,
                      peaks: Dict[str, float]) -> float:
    """Least time of a step's dense products (the attention's five
    projections, the dense layer's feed-forward, the routers, the shared
    experts, the head; the held experts and the attention products apart)
    on ``tokens`` tokens: three passes (forward, input gradient, weight
    gradient) of ``2 p t`` FLOPs, each reading or writing every matrix once;
    the activations' bytes are left out, so it is a floor."""
    p = dense_params_per_token(cfg)
    one = max(2.0 * p * tokens / peaks["bf16_flops_per_s"],
              p * dtype_bytes / peaks["hbm_bytes_per_s"])
    return 3 * one


def expert_min_seconds(cfg: dict, rows_per_step: float, dtype_bytes: int,
                       peaks: Dict[str, float]) -> float:
    """Least time of a step's grouped products over the experts held, all
    expert layers together sending them ``rows_per_step`` rows: three
    passes (forward, input gradient, weight gradient), each reading or
    writing every held expert's matrices once and the rows' activations."""
    h, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    held = int(cfg.get("experts_held", cfg["num_experts"]))
    layers = blocks(cfg)["expert"]
    flops = 2.0 * matrices(cfg)["expert"] * rows_per_step
    weights = layers * held * matrices(cfg)["expert"] * dtype_bytes
    acts = rows_per_step * (2 * h + 3 * f) * dtype_bytes
    one = max(flops / peaks["bf16_flops_per_s"],
              (weights + acts) / peaks["hbm_bytes_per_s"])
    return 3 * one
