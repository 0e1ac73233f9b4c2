"""FLOPs and least bytes that a training step of a DeepSeek-V3-family decoder
requires, counted from the configuration's shapes alone.

The counts are the yardstick's, not the compiler's. One multiply-add is two
FLOPs. A matrix product's backward pass is two products of the forward's size,
so training a matrix of ``p`` parameters on ``t`` tokens takes ``6 p t``.
Causal attention over ``s`` positions takes, forward, ``s^2 / 2`` score
entries a head, each ``2 (d_qk + d_v)`` FLOPs; its backward pass twice that.
Nothing recomputed is counted (not the rematerialised forward, not the
scores the flash backward rebuilds). The experts held are counted at the rows
really routed to them (``moe_local_rows``, the program's counter), summed
over the expert layers.

``cfg`` is the model's view of a configuration: ``n_routed_experts`` the
router's width, ``experts_held`` this rank's share.
"""

from __future__ import annotations

from typing import Dict


def blocks(cfg: dict) -> Dict[str, int]:
    """How many blocks of each kind the model applies."""
    dense = int(cfg.get("first_k_dense_replace", 0))
    mtp = int(cfg.get("num_nextn_predict_layers", 0))
    layers = int(cfg["num_hidden_layers"])
    return {"dense": dense, "expert": layers - dense + mtp,
            "all": layers + mtp, "mtp": mtp}


def matrices(cfg: dict) -> Dict[str, int]:
    """Parameters of each kind of matrix, one copy."""
    h, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    ql, kl = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    dn, dr, dv = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                  int(cfg["v_head_dim"]))
    f = int(cfg["moe_intermediate_size"])
    return {
        "mla": h * ql + ql * heads * (dn + dr) + h * (kl + dr)
        + kl * heads * (dn + dv) + heads * dv * h,
        "dense_ffn": 3 * h * int(cfg["intermediate_size"]),
        "router": h * int(cfg["n_routed_experts"]),
        "shared": 3 * h * f * int(cfg.get("n_shared_experts", 0)),
        "expert": 3 * h * f,
        "mtp_merge": 2 * h * h,
        "head": h * int(cfg["vocab_size"]),
    }


def norm_params(cfg: dict) -> int:
    b = blocks(cfg)
    per_block = 2 * int(cfg["hidden_size"]) + int(cfg["q_lora_rank"]) \
        + int(cfg["kv_lora_rank"])
    return b["all"] * per_block + int(cfg["hidden_size"]) * (1 + 3 * b["mtp"])


def param_count(cfg: dict) -> int:
    """Parameters this rank holds (the correction bias is state, not one)."""
    m, b = matrices(cfg), blocks(cfg)
    held = int(cfg.get("experts_held", cfg["n_routed_experts"]))
    return (b["all"] * m["mla"] + b["dense"] * m["dense_ffn"]
            + b["expert"] * (m["router"] + m["shared"] + held * m["expert"])
            + b["mtp"] * m["mtp_merge"] + 2 * m["head"] + norm_params(cfg))


def dense_params_per_token(cfg: dict) -> int:
    """Matrix parameters every token passes through (the held experts
    apart): the embedding is a lookup, the head runs once a prediction
    head."""
    m, b = matrices(cfg), blocks(cfg)
    return (b["all"] * m["mla"] + b["dense"] * m["dense_ffn"]
            + b["expert"] * (m["router"] + m["shared"])
            + b["mtp"] * m["mtp_merge"] + (1 + b["mtp"]) * m["head"])


def attention_flops_per_sequence(cfg: dict, seq: int) -> Dict[str, float]:
    """One block's causal attention products on one sequence."""
    d = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]) \
        + int(cfg["v_head_dim"])
    fwd = seq * seq / 2 * 2 * int(cfg["num_attention_heads"]) * d
    return {"fwd": fwd, "bwd": 2 * fwd}


def train_flops_per_sample(cfg: dict, seq: int,
                           local_rows_per_sample: float) -> float:
    """Forward and backward FLOPs one sequence of ``seq`` tokens requires;
    ``local_rows_per_sample`` the token-choices it sends to the experts held,
    summed over the expert layers."""
    att = attention_flops_per_sequence(cfg, seq)
    return (6.0 * dense_params_per_token(cfg) * seq
            + 6.0 * matrices(cfg)["expert"] * local_rows_per_sample
            + blocks(cfg)["all"] * (att["fwd"] + att["bwd"]))


def attention_min_seconds(cfg: dict, seq: int, sequences: int,
                          dtype_bytes: int, peaks: Dict[str, float]) -> float:
    """Least time of a step's attention products, all blocks: per pass the
    larger of FLOPs over the peak rate and the bytes of q, k, v and the
    output (and their gradients, backward) over the HBM rate."""
    heads = int(cfg["num_attention_heads"])
    d_qk = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    d_v = int(cfg["v_head_dim"])
    att = attention_flops_per_sequence(cfg, seq)
    io = seq * heads * (2 * d_qk + 2 * d_v) * dtype_bytes
    total = 0.0
    for flops, nbytes in ((att["fwd"], io), (att["bwd"], 2 * io)):
        total += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return total * sequences * blocks(cfg)["all"]


def dense_min_seconds(cfg: dict, tokens: int, dtype_bytes: int,
                      peaks: Dict[str, float]) -> float:
    """Least time of a step's dense products (the MLA projections, layer
    0's feed-forward, the routers, the shared experts, the MTP merge, the
    two heads; the held experts and the attention products apart) on
    ``tokens`` tokens: three passes (forward, input gradient, weight
    gradient) of ``2 p t`` FLOPs, each reading or writing every matrix
    once; the activations' bytes are left out, so it is a floor."""
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
    held = int(cfg.get("experts_held", cfg["n_routed_experts"]))
    layers = blocks(cfg)["expert"]
    flops = 2.0 * matrices(cfg)["expert"] * rows_per_step
    weights = layers * held * matrices(cfg)["expert"] * dtype_bytes
    acts = rows_per_step * (2 * h + 3 * f) * dtype_bytes
    one = max(flops / peaks["bf16_flops_per_s"],
              (weights + acts) / peaks["hbm_bytes_per_s"])
    return 3 * one
