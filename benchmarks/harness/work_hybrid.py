"""FLOPs and least bytes that a training step of a ``nemotron_h``-family
decoder (Mamba-2 mixers, plain grouped-query attention, latent-space expert
layers with two-matrix experts, one mixer a block) requires, counted from the
configuration's shapes alone: ``work_lm.py``'s rules at this family's shapes.

One multiply-add is two FLOPs; training a matrix of ``p`` parameters on ``t``
tokens takes ``6 p t``. Attention is counted at the causal mask's own score
entries (query t sees ``t + 1`` keys; an entry costs ``2 (d + d)`` FLOPs
forward in each query head, its backward pass twice that). The scan is
counted as the chunked (SSD) products at the configuration's ``chunk_size``
Q, over the entries inside the causal mask of a chunk: a position i of a
chunk takes ``C_i . B_j`` (2 N a group) and ``w_ij x_j`` (2 P a head) for its
``i + 1`` positions j, and its share of the chunk's end state (``x_j B_j^T``,
2 P N a head) and of reading the state the chunk starts with (2 P N a head);
backward twice that. Nothing recomputed is counted (not the rematerialised
forward, not the scores a flash backward rebuilds). The experts held are
counted at the rows really routed to them (``moe_local_rows``, the program's
counter), summed over the expert layers.

``cfg`` is the model's view of a configuration (the factory's
``model_config``): the published head counts with ``mixer_parallel_size``
beside them, ``n_routed_experts`` the router's width, ``experts_held`` this
rank's share, ``hybrid_override_pattern`` the letters of the layers it has.
"""

from __future__ import annotations

from typing import Dict

KINDS = ("M", "*", "E")


def sizes(cfg: dict) -> Dict[str, int]:
    """What this rank holds of each layer."""
    t = int(cfg.get("mixer_parallel_size", 1))
    hidden = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    e = int(cfg["n_routed_experts"])
    return {
        "hidden": hidden, "heads": heads // t,
        "kv": max(int(cfg["num_key_value_heads"]) // t, 1),
        "d": int(cfg.get("head_dim", hidden // heads)),
        "m_heads": int(cfg["mamba_num_heads"]) // t,
        "m_dim": int(cfg["mamba_head_dim"]),
        "groups": int(cfg["n_groups"]) // t,
        "state": int(cfg["ssm_state_size"]),
        "taps": int(cfg["conv_kernel"]), "chunk": int(cfg["chunk_size"]),
        "latent": int(cfg["moe_latent_size"]),
        "f": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["moe_shared_expert_intermediate_size"])
        if cfg.get("n_shared_experts", 0) else 0,
        "experts": e, "held": int(cfg.get("experts_held", e)),
        "vocab": int(cfg["vocab_size"])}


def blocks(cfg: dict) -> Dict[str, int]:
    """How many blocks of each kind the model applies (no MTP module is
    counted: the cell's configuration has none)."""
    kinds = cfg["hybrid_override_pattern"][:int(cfg["num_hidden_layers"])]
    return {"all": len(kinds), **{k: kinds.count(k) for k in KINDS}}


def matrices(cfg: dict) -> Dict[str, int]:
    """Parameters of each kind of matrix, one copy."""
    z = sizes(cfg)
    h, inner = z["hidden"], z["m_heads"] * z["m_dim"]
    conv_dim = inner + 2 * z["groups"] * z["state"]
    return {
        # in_proj [z | x | B | C | dt] and out_proj
        "mamba": h * (inner + conv_dim + z["m_heads"]) + inner * h,
        # q and o at the query heads' count, k and v at the kv heads'
        "attention": 2 * h * z["heads"] * z["d"] + 2 * h * z["kv"] * z["d"],
        "router": h * z["experts"],
        "latent": 2 * h * z["latent"],
        "shared": 2 * h * z["shared"],
        "expert": 2 * z["latent"] * z["f"],
        "head": h * z["vocab"]}


def small_params(cfg: dict) -> int:
    """A norm a block and the final norm; a Mamba-2 mixer's convolution
    (taps and bias), ``A_log``, ``dt_bias``, ``D`` and its gated norm."""
    z, b = sizes(cfg), blocks(cfg)
    inner = z["m_heads"] * z["m_dim"]
    conv_dim = inner + 2 * z["groups"] * z["state"]
    return (b["all"] + 1) * z["hidden"] + b["M"] * (
        (z["taps"] + 1) * conv_dim + 3 * z["m_heads"] + inner)


def param_count(cfg: dict) -> int:
    """Parameters this rank holds (the expert bias is state, not one)."""
    m, b = matrices(cfg), blocks(cfg)
    return (b["M"] * m["mamba"] + b["*"] * m["attention"]
            + b["E"] * (m["router"] + m["latent"] + m["shared"]
                        + sizes(cfg)["held"] * m["expert"])
            + 2 * m["head"] + small_params(cfg))


def dense_params_per_token(cfg: dict) -> int:
    """Matrix parameters every token passes through (the held experts
    apart): the embedding is a lookup."""
    m, b = matrices(cfg), blocks(cfg)
    return (b["M"] * m["mamba"] + b["*"] * m["attention"]
            + b["E"] * (m["router"] + m["latent"] + m["shared"]) + m["head"])


def attention_flops_per_sequence(cfg: dict, seq: int) -> Dict[str, float]:
    """One attention block's products on one sequence."""
    z = sizes(cfg)
    fwd = float(seq * (seq + 1) // 2) * 2 * 2 * z["d"] * z["heads"]
    return {"fwd": fwd, "bwd": 2 * fwd}


def scan_flops_per_sequence(cfg: dict, seq: int) -> Dict[str, float]:
    """One Mamba-2 mixer's scan on one sequence, as the chunked products."""
    z = sizes(cfg)
    q = min(z["chunk"], seq)
    masked = (seq // q) * (q * (q + 1) // 2)      # (i, j) pairs, j <= i
    fwd = float(masked * 2 * (z["groups"] * z["state"]
                              + z["m_heads"] * z["m_dim"])
                + seq * 2 * 2 * z["m_heads"] * z["m_dim"] * z["state"])
    return {"fwd": fwd, "bwd": 2 * fwd}


def train_flops_per_sample(cfg: dict, seq: int,
                           local_rows_per_sample: float) -> float:
    """Forward and backward FLOPs one sequence of ``seq`` tokens requires;
    ``local_rows_per_sample`` the token-choices it sends to the experts held,
    summed over the expert layers."""
    b = blocks(cfg)
    att = attention_flops_per_sequence(cfg, seq)
    scan = scan_flops_per_sequence(cfg, seq)
    return (6.0 * dense_params_per_token(cfg) * seq
            + 6.0 * matrices(cfg)["expert"] * local_rows_per_sample
            + b["*"] * (att["fwd"] + att["bwd"])
            + b["M"] * (scan["fwd"] + scan["bwd"]))


def attention_min_seconds(cfg: dict, seq: int, sequences: int,
                          dtype_bytes: int, peaks: Dict[str, float]) -> float:
    """Least time of a step's attention products in all attention blocks:
    per pass the larger of FLOPs over the peak rate and the bytes of q and
    the output (query heads), k and v (kv heads) over the HBM rate; their
    gradients too, backward."""
    z = sizes(cfg)
    att = attention_flops_per_sequence(cfg, seq)
    io = seq * 2 * (z["heads"] + z["kv"]) * z["d"] * dtype_bytes
    total = 0.0
    for flops, nbytes in ((att["fwd"], io), (att["bwd"], 2 * io)):
        total += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return total * sequences * blocks(cfg)["*"]


def scan_min_seconds(cfg: dict, seq: int, sequences: int, dtype_bytes: int,
                     peaks: Dict[str, float]) -> float:
    """Least time of a step's scans in all Mamba-2 blocks: per pass the
    larger of the chunked products' FLOPs over the peak rate and the bytes of
    ``x'``, ``B``, ``C``, ``dt`` and ``z`` in and ``y`` out over the HBM
    rate; their gradients too, backward. Nothing recomputed."""
    z = sizes(cfg)
    inner = z["m_heads"] * z["m_dim"]
    scan = scan_flops_per_sequence(cfg, seq)
    io = seq * (3 * inner + 2 * z["groups"] * z["state"] + z["m_heads"]) \
        * dtype_bytes
    total = 0.0
    for flops, nbytes in ((scan["fwd"], io), (scan["bwd"], 2 * io)):
        total += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return total * sequences * blocks(cfg)["M"]


def dense_min_seconds(cfg: dict, tokens: int, dtype_bytes: int,
                      peaks: Dict[str, float]) -> float:
    """Least time of a step's dense products (the mixers' in and out
    projections, q/k/v/o, the routers, the latent projections, the shared
    experts, the head; the held experts, the attention products and the
    scan apart) on ``tokens`` tokens: three passes (forward, input gradient,
    weight gradient) of ``2 p t`` FLOPs, each reading or writing every
    matrix once; the activations' bytes are left out, so it is a floor."""
    p = dense_params_per_token(cfg)
    one = max(2.0 * p * tokens / peaks["bf16_flops_per_s"],
              p * dtype_bytes / peaks["hbm_bytes_per_s"])
    return 3 * one


def expert_min_seconds(cfg: dict, rows_per_step: float, dtype_bytes: int,
                       peaks: Dict[str, float]) -> float:
    """Least time of a step's grouped products over the experts held (two
    products an expert, rows of the latent width), all expert layers
    together sending them ``rows_per_step`` rows: three passes (forward,
    input gradient, weight gradient), each reading or writing every held
    expert's matrices once and the rows' activations (the row in and out,
    the expert's width before and after its activation)."""
    z = sizes(cfg)
    layers = blocks(cfg)["E"]
    flops = 2.0 * matrices(cfg)["expert"] * rows_per_step
    weights = layers * z["held"] * matrices(cfg)["expert"] * dtype_bytes
    acts = rows_per_step * (2 * z["latent"] + 2 * z["f"]) * dtype_bytes
    one = max(flops / peaks["bf16_flops_per_s"],
              (weights + acts) / peaks["hbm_bytes_per_s"])
    return 3 * one
