"""FLOPs and least times that a training step of a ``KeyeVL2``-family decoder
(learned sparse attention: a lightning indexer picks each query's top-k keys,
the attention reads those alone, the indexer learns by a KL loss) requires,
counted from the configuration's shapes alone, at the SELECTED entries.

One multiply-add is two FLOPs; training a matrix of ``p`` parameters on ``t``
positions takes ``6 p t`` (the indexer's three projections ``4 p t``: their
input is detached, so no gradient flows back through them). A sequence of L
positions, H query heads of d, the indexer's h^I heads of d^I, top-k keys:

* selected entries a head: ``sum_t min(t + 1, topk)``; dense causal ones
  ``L (L + 1) / 2``;
* attention: ``2 (d + d)`` FLOPs an entry and query head forward (q.k and
  p.v), twice that backward, at the selected entries;
* the indexer's scores: ``2 h^I d^I`` an entry, over every causal entry (it
  has to score them to choose); their gradient: ``2 x 2 h^I d^I`` (q^I's and
  k^I's), at the selected entries alone, where L_I has a gradient;
* the KL's target: one more q.k in every query head at the selected
  entries, ``2 d H``.

Nothing recomputed is counted (not the rematerialised forward, not the
scores the flash backward rebuilds, not a walked tile's unselected entries,
not the indexer's second scoring for its loss). The experts held are counted
at the rows really routed to them (``moe_local_rows``), summed over the
expert layers; their least time is ``work_gqa.expert_min_seconds`` (the
model's view names every layer ``full_attention`` for it).
"""

from __future__ import annotations

from typing import Dict


def _sa(cfg: dict) -> Dict[str, int]:
    sa = cfg["sa_config"]
    return {"hi": int(sa["indexer_num_heads"]),
            "di": int(sa["indexer_head_dim"]), "topk": int(sa["topk"])}


def matrices(cfg: dict) -> Dict[str, int]:
    """Parameters of each kind of matrix, one copy."""
    h, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kv, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    sa = _sa(cfg)
    return {
        "attention": 2 * h * heads * d + 2 * h * kv * d,
        # q^I, k^I and the head weights
        "indexer": h * sa["hi"] * sa["di"] + h * sa["di"] + h * sa["hi"],
        "router": h * int(cfg["num_experts"]),
        "expert": 3 * h * int(cfg["moe_intermediate_size"]),
        "head": h * int(cfg["vocab_size"]),
    }


def param_count(cfg: dict) -> int:
    """Parameters this rank holds: each layer's attention with its q and k
    norms, indexer with its key's LayerNorm, router, held experts and two
    norms; the embedding, the head, the final norm."""
    m, h = matrices(cfg), int(cfg["hidden_size"])
    held = int(cfg.get("experts_held", cfg["num_experts"]))
    layer = m["attention"] + m["indexer"] + m["router"] \
        + held * m["expert"] + 2 * h + 2 * int(cfg["head_dim"]) \
        + 2 * _sa(cfg)["di"]
    return int(cfg["num_hidden_layers"]) * layer \
        + int(cfg["vocab_size"]) * h + m["head"] + h


def selected_entries(length: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)`` over a sequence's L queries."""
    k = min(topk, length)
    return k * (k + 1) // 2 + (length - k) * k


def causal_entries(length: int) -> int:
    return length * (length + 1) // 2


def mechanism_flops_per_sequence(cfg: dict, length: int
                                 ) -> Dict[str, float]:
    """One layer's learned-sparse-attention products on one sequence:
    ``attention`` (forward and backward at the selected entries),
    ``indexer`` (its scores at the causal entries, their gradient and the
    KL's target at the selected ones)."""
    sa = _sa(cfg)
    heads, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    sel = float(selected_entries(length, sa["topk"]))
    att = sel * 2 * 2 * d * heads
    idx = float(causal_entries(length)) * 2 * sa["hi"] * sa["di"] \
        + sel * 2 * 2 * sa["hi"] * sa["di"] + sel * 2 * d * heads
    return {"attention": 3 * att, "indexer": idx}


def train_flops_per_sample(cfg: dict, length: int,
                           local_rows_per_sample: float) -> float:
    """Forward and backward FLOPs one sequence of ``length`` tokens
    requires; ``local_rows_per_sample`` the token-choices its positions send
    to the experts held, summed over the expert layers."""
    m, layers = matrices(cfg), int(cfg["num_hidden_layers"])
    mech = mechanism_flops_per_sequence(cfg, length)
    return (6.0 * layers * (m["attention"] + m["router"]) * length
            + 4.0 * layers * m["indexer"] * length
            + 6.0 * m["head"] * length
            + 6.0 * m["expert"] * local_rows_per_sample
            + layers * (mech["attention"] + mech["indexer"]))


def _least(flops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def attention_min_seconds(cfg: dict, length: int, sequences: int,
                          dtype_bytes: int, peaks: Dict[str, float]) -> float:
    """Least time of a step's selected-key attention products in all
    layers: per pass the larger of FLOPs over the peak rate and the bytes of
    q and the output (query heads), k and v (kv heads) over the HBM rate;
    their gradients too, backward."""
    heads, kv = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    fwd = mechanism_flops_per_sequence(cfg, length)["attention"] / 3
    io = length * 2 * (heads + kv) * int(cfg["head_dim"]) * dtype_bytes
    total = _least(fwd, io, peaks) + _least(2 * fwd, 2 * io, peaks)
    return total * sequences * int(cfg["num_hidden_layers"])


def indexer_min_seconds(cfg: dict, length: int, sequences: int,
                        dtype_bytes: int, peaks: Dict[str, float]) -> float:
    """Least time of a step's indexer products in all layers: its scores,
    their gradient and the KL's target (``mechanism_flops_per_sequence``),
    or the bytes of q^I, k^I, the main q and k and the packed selection
    over the HBM rate, whichever is larger."""
    sa = _sa(cfg)
    heads, kv = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    flops = mechanism_flops_per_sequence(cfg, length)["indexer"]
    nbytes = length * ((sa["hi"] + 1) * sa["di"] + (heads + kv)
                       * int(cfg["head_dim"])) * dtype_bytes \
        + length * length / 8
    return _least(flops, nbytes, peaks) * sequences \
        * int(cfg["num_hidden_layers"])


def dense_min_seconds(cfg: dict, tokens: int, dtype_bytes: int,
                      peaks: Dict[str, float]) -> float:
    """Least time of a step's dense products outside ``sa.indexer`` (the
    attention's four projections, the routers, the head; the indexer's
    projections, the held experts and the attention products apart) on
    ``tokens`` tokens: three passes (forward, input gradient, weight
    gradient) of ``2 p t`` FLOPs, each reading or writing every matrix once;
    the activations' bytes are left out, so it is a floor."""
    m, layers = matrices(cfg), int(cfg["num_hidden_layers"])
    p = layers * (m["attention"] + m["router"]) + m["head"]
    return 3 * _least(2.0 * p * tokens, p * dtype_bytes, peaks)
