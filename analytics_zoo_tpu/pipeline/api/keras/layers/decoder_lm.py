"""A decoder language model of three published families, chosen by the keys
of the ``config.json`` it is built from (:meth:`DecoderLM.from_config`):

* the DeepSeek-V3 family: pre-norm, RMSNorm, interleaved RoPE, multi-head
  latent attention (MLA), SwiGLU, leading dense blocks, sparse-expert blocks
  with a shared expert, and a multi-token-prediction (MTP) module that shares
  the embedding and the output head;
* the ``afmoe`` family (a ``layer_types`` key): sandwich norms, gated
  grouped-query attention with q/k norms whose layers are, by
  ``layer_types``, sliding-window (rotate-half RoPE, a causal window) or
  global (causal, no position encoding), the embedding scaled by
  ``sqrt(hidden)``, the same expert layer, no MTP module;
* the ``nemotron_h`` family (a ``hybrid_override_pattern`` key): every block
  is ONE mixer behind one norm, by the pattern's letter a Mamba-2 mixer
  (``M``), plain grouped-query attention (``*``: no gate, no q/k norms, no
  position encoding) or the expert layer in its latent form (``E``: experts
  of two matrices and a squared ReLU in a ``moe_latent_size``-wide space, a
  shared expert of its own width on the hidden rows); an MTP module built
  from ``mtp_hybrid_override_pattern``.

Trained through the estimator like any other module::

    model = DecoderLM.from_config(cfg)
    est = TPUEstimator(model, loss=model.loss(), optimizer=AdamWeightDecay(...))
    est.fit({"x": ids, "y": ids}, epochs=..., batch_size=...)

``ids`` are (batch, seq) token ids; the labels are the same ids, shifted on
the device: by one for the main head, by two for the MTP head. Attention is
causal over the whole sequence (no cross-document mask, as the family trains).

Layer equations (x: (batch, seq, hidden)):

* block: ``x = x + MLA(RMSNorm(x))``; ``x = x + FFN(RMSNorm(x))``;
* MLA, training form: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads x
  (nope | rope); ``[c_kv | k_r] = x W_kva``; ``[k_nope | v] = RMSNorm(c_kv)
  W_kvb``; RoPE on q's rope part and on ``k_r``, which all heads share;
  ``softmax_causal(q k^T / sqrt(d_nope + d_rope)) v`` through the flash
  kernel, which takes v's head size beside q's and k's; ``W_o``;
* expert layer: ``parallel/expert_parallel.py`` (sigmoid scores over all
  experts, top-k by score + correction bias, renormalised and scaled gates;
  the experts held here computed by grouped products; a shared expert).
  The correction bias lives in the ``router_state`` collection and is
  updated by the step itself (no gradient); the layer's counters in
  ``moe_stats``. The engine carries both as it carries BatchNorm's
  statistics;
* MTP (depth 1): ``h' = W_eh [RMSNorm(h_t) | RMSNorm(Emb(tok_{t+1}))]``, one
  more block, a norm, the main model's embedding and head, predicting
  ``tok_{t+2}``;
* ``afmoe``: ``x = Emb(ids) sqrt(hidden)``; block ``x = x + N(Attn(N(x)))``;
  ``x = x + N(FFN(N(x)))`` (four norms a block); ``q = x W_q``, ``k = x
  W_k``, ``v = x W_v``, ``g = x W_g``; RMSNorm over each head's width on q
  and k; on sliding layers RoPE and ``window``; each run of ``heads /
  kv_heads`` query heads reads one key/value head, inside the flash kernel;
  ``out = (softmax(q k^T / sqrt(d)) v * sigmoid(g)) W_o``.

* ``nemotron_h``: ``x = x + Mixer(RMSNorm(x))`` a block. ``M``: ``[z | xBC |
  dt] = x W_in``; ``xBC = silu(conv1d_causal(xBC))`` (depthwise, with bias);
  ``[x' | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  a head's state ``S_t = exp(dt_t A) S_{t-1} + dt_t x'_t B_t^T``, ``y_t = S_t
  C_t + D x'_t`` over the whole sequence (``ops/ssm.py``, in chunks of
  ``chunk_size``); ``y = RMSNorm_grouped(y * silu(z))`` over each group's
  channels; ``y W_out``. ``E``: the router scores the hidden rows; ``u = x
  W_down_latent``; ``r = sum_k gate_k W2_k relu(W1_k u)^2`` over the experts
  held; ``r W_up_latent + W2_s relu(W1_s x)^2``.

One rank of an expert-parallel deployment holds ``experts_held`` of the
``n_routed_experts`` experts of each layer, from ``first_expert`` on: the
router keeps its full width and the layer computes its own experts' part.
One rank of a ``mixer_parallel_size``-way tensor-parallel deployment of the
``nemotron_h`` mixers holds that share of the Mamba-2 heads with their B/C
and norm groups, and of the query heads with the kv head they read: the
matching columns of the input projections and rows of the output
projection, whose result is then a partial sum. On one chip either layer
runs without its exchange.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.obs.registry import REGISTRY as _REGISTRY
from analytics_zoo_tpu.ops.attention import (
    FLASH_RESIDUAL_NAMES, flash_attention)
from analytics_zoo_tpu.ops.ssm import causal_conv1d, ssd_scan
from analytics_zoo_tpu.parallel.expert_parallel import (
    ROUTER_CHOICE_NAME, expert_load, held_experts_ffn, noaux_bias_update,
    route_noaux_tc)
from ..engine.graph import keras_call

_GAUGE_DOC = {
    "moe_local_rows": "token-choices routed to the experts held, a step, "
                      "summed over the expert layers (last read)",
    "moe_rows_max_over_mean": "largest held expert's rows over the held "
                              "experts' mean, worst layer, last step read",
    "moe_dropped_rows": "token-choices routed here and not computed since "
                        "the state was made: 0 by construction",
    "moe_rows_moved_over_routed": "rows the expert layers' gathers fetched "
                                  "over the token-choices routed here, all "
                                  "steps counted: 1 is no padding moved",
}

INIT_POSITIONS = 128
ROUTER_STATE = "router_state"      # the correction bias: state, not parameter
MOE_STATS = "moe_stats"            # counters the step leaves in its outputs


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                                + self.eps)
        return (y * w).astype(self.dtype)


def _rope_angles(x, theta: float):
    """cos and sin of ``pos * theta**(-2i/d)``, (1, seq, 1, d/2), float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]


def rope_interleaved(x, theta: float):
    """Rotary position embedding over adjacent pairs ``(x[2i], x[2i+1])`` of
    the last axis, angle ``pos * theta**(-2i/d)``. x: (batch, seq, heads, d);
    computed in float32."""
    cos, sin = _rope_angles(x, theta)
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def rope_half(x, theta: float):
    """Rotary position embedding over the pairs ``(x[i], x[i + d/2])`` of the
    last axis (rotate-half), angle ``pos * theta**(-2i/d)``. x: (batch, seq,
    heads, d); computed in float32."""
    d = x.shape[-1]
    cos, sin = _rope_angles(x, theta)
    x32 = x.astype(jnp.float32)
    lo, hi = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           -1).astype(x.dtype)


def _dense(features: int, dtype, name: str, std: float = 0.02):
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32,
                    kernel_init=nn.initializers.normal(std), name=name)


class MLAttention(nn.Module):
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    eps: float
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        h, dn, dr, dv = (self.num_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim, self.v_head_dim)
        norm = functools.partial(RMSNorm, self.eps, self.dtype)
        with jax.named_scope("attn.mla"):
            c_q = norm(name="q_a_layernorm")(
                _dense(self.q_lora_rank, self.dtype, "q_a_proj")(x))
            q = _dense(h * (dn + dr), self.dtype, "q_b_proj")(c_q)
            q = q.reshape(b, s, h, dn + dr)
            ckv = _dense(self.kv_lora_rank + dr, self.dtype,
                         "kv_a_proj_with_mqa")(x)
            c_kv = norm(name="kv_a_layernorm")(ckv[..., :self.kv_lora_rank])
            k_r = ckv[..., self.kv_lora_rank:].reshape(b, s, 1, dr)
            kv = _dense(h * (dn + dv), self.dtype, "kv_b_proj")(c_kv)
            kv = kv.reshape(b, s, h, dn + dv)
            q = jnp.concatenate(
                [q[..., :dn], rope_interleaved(q[..., dn:], self.rope_theta)],
                axis=-1)
            k_r = rope_interleaved(k_r, self.rope_theta)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_r, (b, s, h, dr))], axis=-1)
            out = flash_attention(q, k, kv[..., dn:], causal=True,
                                  sm_scale=1.0 / math.sqrt(dn + dr))
            return _dense(hidden, self.dtype, "o_proj")(
                out.reshape(b, s, h * dv))


class GQAttention(nn.Module):
    """Grouped-query attention, by default gated and with q/k norms (the
    ``afmoe`` family's; ``gated`` and ``qk_norm`` off leave the plain form).
    ``window`` makes the layer a sliding one (RoPE, the causal window);
    without it the layer is global: causal, no position encoding. k and v go
    to the flash kernel with their own ``num_kv_heads``."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    eps: float
    window: Optional[int] = None
    gated: bool = True
    qk_norm: bool = True
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        norm = functools.partial(RMSNorm, self.eps, self.dtype)
        with jax.named_scope("attn.gqa"):
            q = _dense(h * d, self.dtype, "q_proj")(x).reshape(b, s, h, d)
            k = _dense(hk * d, self.dtype, "k_proj")(x).reshape(b, s, hk, d)
            v = _dense(hk * d, self.dtype, "v_proj")(x).reshape(b, s, hk, d)
            if self.gated:
                gate = _dense(h * d, self.dtype, "gate_proj")(x)
            if self.qk_norm:
                q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
            if self.window is not None:
                q, k = (rope_half(t, self.rope_theta) for t in (q, k))
            with jax.named_scope("attn.global" if self.window is None
                                 else "attn.window"):
                out = flash_attention(q, k, v, causal=True,
                                      window=self.window,
                                      sm_scale=1.0 / math.sqrt(d))
            out = out.reshape(b, s, h * d)
            if self.gated:
                out = out * jax.nn.sigmoid(gate)
            return _dense(hidden, self.dtype, "o_proj")(out)


_ATTENTION = {"mla": MLAttention, "gqa": GQAttention}


def relu_squared(x):
    return jnp.square(jax.nn.relu(x))


_ACTIVATIONS = {"silu": jax.nn.silu, "relu2": relu_squared}


class SwiGLU(nn.Module):
    """``(act(x W_gate) * x W_up) W_down``; with ``gated`` off the feed-forward
    of two matrices, ``act(x W_up) W_down``."""
    width: int
    dtype: Any = jnp.bfloat16
    activation: str = "silu"
    gated: bool = True

    @nn.compact
    def __call__(self, x):
        act = _ACTIVATIONS[self.activation]
        if self.gated:
            gate = _dense(self.width, self.dtype, "gate_proj")(x)
        up = _dense(self.width, self.dtype, "up_proj")(x)
        return _dense(x.shape[-1], self.dtype, "down_proj")(
            act(gate) * up if self.gated else act(up))


def _log_uniform(lo: float, hi: float):
    """log of a uniform draw in [lo, hi] (``A_log``)."""
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, dtype, lo, hi))
    return init


def _inverse_softplus_log_uniform(lo: float, hi: float):
    """The inverse softplus of a log-uniform draw in [lo, hi] (``dt_bias``:
    ``softplus(dt_bias)`` is the draw)."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(lo),
                                        math.log(hi)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


class Mamba2Mixer(nn.Module):
    """A Mamba-2 mixer, or the share of one that ``num_heads`` of its heads
    with their ``n_groups`` B/C and norm groups make (the module docstring
    has the equations). ``in_proj``'s columns are ``[z | x' | B | C | dt]``;
    the convolution runs over ``[x' | B | C]``; the gated norm is over each
    group's ``num_heads * head_dim / n_groups`` channels."""
    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int
    chunk_size: int
    eps: float
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        h, p, g, n = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, f32 = h * p, jnp.float32
        conv_dim = inner + 2 * g * n
        with jax.named_scope("ssm.mixer"):
            zxbcdt = _dense(inner + conv_dim + h, self.dtype, "in_proj")(x)
            conv_w = self.param(
                "conv1d_weight", nn.initializers.normal(
                    1.0 / math.sqrt(self.conv_kernel)),
                (self.conv_kernel, conv_dim))
            conv_b = self.param("conv1d_bias", nn.initializers.zeros,
                                (conv_dim,))
            a_log = self.param("A_log", _log_uniform(1.0, 16.0), (h,))
            dt_bias = self.param("dt_bias", _inverse_softplus_log_uniform(
                1e-3, 1e-1), (h,))
            d = self.param("D", nn.initializers.ones, (h,))
            norm_w = self.param("norm_weight", nn.initializers.ones, (inner,))
            z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
            if self.is_initializing():
                # no parameter's shape depends on the scan: the engine's
                # eager init does not compile it for its prefix
                y = z
            else:
                xbc = jax.nn.silu(causal_conv1d(xbc, conv_w, conv_b))
                xs, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
                y = ssd_scan(
                    xs.reshape(b, s, h, p),
                    jax.nn.softplus(dt.astype(f32) + dt_bias),
                    -jnp.exp(a_log.astype(f32)), bm.reshape(b, s, g, n),
                    cm.reshape(b, s, g, n), d,
                    chunk_size=self.chunk_size).reshape(b, s, inner)
            y = (y.astype(f32) * jax.nn.silu(z.astype(f32))).reshape(
                b, s, g, inner // g)
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                                  + self.eps)
            y = (y.reshape(b, s, inner) * norm_w).astype(self.dtype)
            return _dense(hidden, self.dtype, "out_proj")(y)


class SparseExperts(nn.Module):
    """The expert layer, as the rank that holds ``experts_held`` experts
    from ``first_expert`` on computes it, plus the shared expert. With a
    ``latent_size`` the routed experts work on rows of that width, between a
    projection down and one up (the router and the shared expert read the
    hidden rows); ``gated`` off makes every expert, the shared one too,
    ``act(x W_up) W_down``; ``shared_width`` is the shared expert's own
    (else ``moe_intermediate_size * n_shared_experts``)."""
    n_routed_experts: int
    experts_held: int
    first_expert: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int
    routed_scaling_factor: float
    bias_update_rate: float
    dtype: Any = jnp.bfloat16
    latent_size: int = 0
    activation: str = "silu"
    gated: bool = True
    shared_width: int = 0

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        e, held, f = (self.n_routed_experts, self.experts_held,
                      self.moe_intermediate_size)
        d = self.latent_size or hidden       # the routed experts' rows
        flat = x.reshape(b * s, hidden)
        router = self.param("gate", nn.initializers.normal(0.02), (hidden, e))
        bias = self.variable(ROUTER_STATE, "e_score_correction_bias",
                             jnp.zeros, (e,), jnp.float32)
        init = nn.initializers.normal(0.02)
        w_gate = self.param("experts_gate_proj", init, (held, d, f)) \
            if self.gated else None
        w_up = self.param("experts_up_proj", init, (held, d, f))
        w_down = self.param("experts_down_proj", init, (held, f, d))
        with jax.named_scope("moe.router"):
            idx, gates = route_noaux_tc(
                flat, router, bias.value, top_k=self.num_experts_per_tok,
                scaling=self.routed_scaling_factor)
        rows = flat
        if self.latent_size:
            with jax.named_scope("moe.latent"):
                rows = _dense(d, self.dtype, "latent_down_proj")(flat)
        if self.is_initializing():
            # no parameter's shape depends on the held experts' output, and
            # the engine's eager init would compile every operation of the
            # dispatch for its prefix's shapes: seconds of set-up a run
            y, counters = jnp.zeros((b * s, d), jnp.float32), None
        else:
            y, counters = held_experts_ffn(
                rows, idx, gates, w_gate, w_up, w_down,
                first_expert=self.first_expert, n_experts=e,
                activation=_ACTIVATIONS[self.activation])
        y = y.astype(self.dtype)
        if self.latent_size:
            with jax.named_scope("moe.latent"):
                y = _dense(hidden, self.dtype, "latent_up_proj")(y)
        y = y.reshape(b, s, hidden)
        if self.n_shared_experts:
            with jax.named_scope("moe.shared"):
                y = y + SwiGLU(
                    self.shared_width or f * self.n_shared_experts,
                    self.dtype, self.activation, self.gated,
                    name="shared_experts")(x)
        self._after_step(bias, idx, counters)
        return y

    def _after_step(self, bias, idx, counters):
        """What a training step leaves behind: the bias moved against the
        step's load, the layer's counters (``load``: the step's
        token-choices for each of ALL experts). Outside training both
        collections are read-only and nothing is written."""
        stats = {
            "rows_total": self.variable(MOE_STATS, "rows_total", jnp.zeros,
                                        (), jnp.float32),
            "rows_moved": self.variable(MOE_STATS, "rows_moved", jnp.zeros,
                                        (), jnp.float32),
            "steps": self.variable(MOE_STATS, "steps", jnp.zeros, (),
                                   jnp.int32),
            "dropped_rows": self.variable(MOE_STATS, "dropped_rows",
                                          jnp.zeros, (), jnp.int32),
            "rows_max_over_mean": self.variable(
                MOE_STATS, "rows_max_over_mean", jnp.zeros, (), jnp.float32),
            "load": self.variable(MOE_STATS, "load", jnp.zeros,
                                  (self.n_routed_experts,), jnp.int32),
        }
        if self.is_initializing():
            return
        with jax.named_scope("moe.router"):
            load = expert_load(idx, self.n_routed_experts)
            if self.is_mutable_collection(ROUTER_STATE):
                bias.value = noaux_bias_update(bias.value, load,
                                               self.bias_update_rate)
        if self.is_mutable_collection(MOE_STATS):
            stats["rows_total"].value += \
                counters["local_rows"].astype(jnp.float32)
            stats["rows_moved"].value += \
                counters["moved_rows"].astype(jnp.float32)
            stats["steps"].value += 1
            stats["dropped_rows"].value += counters["dropped_rows"]
            stats["rows_max_over_mean"].value = counters["rows_max_over_mean"]
            stats["load"].value = load


# one policy object for every block: blocks whose remat parameters are the
# same object share one lowered function, as blocks under a plain remat do.
# It keeps the flash forward kernel's two results and an expert layer's
# routing decision ((tokens, top_k) int32: the backward pass selects nothing)
_KEPT_ACROSS_REMAT = jax.checkpoint_policies.save_only_these_names(
    *FLASH_RESIDUAL_NAMES, ROUTER_CHOICE_NAME)


class DecoderBlock(nn.Module):
    attention: Dict[str, Any]            # its "kind" picks the module
    ffn_width: int                       # the dense block's; 0 for experts
    experts: Optional[Dict[str, Any]]
    eps: float
    sandwich_norms: bool = False         # a norm after each sublayer too
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(RMSNorm, self.eps, self.dtype)
        sizes = dict(self.attention)
        attend = _ATTENTION[sizes.pop("kind", "mla")](
            eps=self.eps, dtype=self.dtype, name="self_attn", **sizes)
        a = attend(norm(name="input_layernorm")(x))
        if self.sandwich_norms:
            x = x + norm(name="post_attention_layernorm")(a)
            h = norm(name="pre_mlp_layernorm")(x)
        else:
            x = x + a
            h = norm(name="post_attention_layernorm")(x)
        if self.experts is None:
            y = SwiGLU(self.ffn_width, self.dtype, name="mlp")(h)
        else:
            y = SparseExperts(dtype=self.dtype, name="mlp",
                              **self.experts)(h)
        if self.sandwich_norms:
            y = norm(name="post_mlp_layernorm")(y)
        return x + y


# a ``nemotron_h`` block's one mixer by its letter in the pattern, and the
# name it takes in the tree (the expert layer's is the one the counters and
# the other families' trees have)
_MIXERS = {"M": (Mamba2Mixer, "mixer"), "*": (GQAttention, "self_attn"),
           "E": (SparseExperts, "mlp")}


class MixerBlock(nn.Module):
    """``x + Mixer(RMSNorm(x))``: ``kind`` a key of ``_MIXERS``, ``sizes``
    that module's."""
    kind: str
    sizes: Dict[str, Any]
    eps: float
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        module, name = _MIXERS[self.kind]
        return x + module(dtype=self.dtype, name=name, **self.sizes)(
            RMSNorm(self.eps, self.dtype, name="norm")(x))


class DecoderLM(nn.Module):
    """ids (batch, seq) -> ``(logits, mtp_logits)``, both (batch, seq, vocab)
    in float32. ``logits[:, t]`` scores ``tok_{t+1}``; ``mtp_logits[:, t]``
    scores ``tok_{t+2}`` (its last position, whose input token lies beyond
    the sequence, is fed token 0 and left out of the loss). With no MTP
    module ``mtp_logits`` is None."""
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    intermediate_size: int
    attention: Any                       # FrozenDict of the attention's sizes
    experts: Any                         # FrozenDict of SparseExperts' sizes
    num_nextn_predict_layers: int = 0
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    mtp_loss_weight: float = 0.3
    # the afmoe family's: each layer's window (None: a global layer), a norm
    # after each sublayer, the embedding's scale
    layer_windows: Optional[tuple] = None
    sandwich_norms: bool = False
    embed_scale: float = 1.0
    # the nemotron_h family's: each block's one mixer by its letter (M, *,
    # E), the MTP module's blocks likewise, the Mamba-2 mixer's sizes
    layer_kinds: Optional[tuple] = None
    mtp_kinds: tuple = ()
    mamba: Any = None

    @classmethod
    def from_config(cls, cfg: Dict[str, Any], **overrides) -> "DecoderLM":
        """From a ``config.json`` of the family. Beside the published keys:
        ``experts_held`` / ``first_expert`` (this rank's share of each
        layer's ``n_routed_experts``; by default all of them),
        ``bias_update_rate`` (gamma), ``mtp_loss_weight`` (lambda),
        ``compute_dtype``; for the ``nemotron_h`` family also
        ``mixer_parallel_size`` / ``mixer_parallel_rank`` (this rank's share
        of each Mamba-2 mixer's and attention layer's heads; by default the
        whole layer)."""
        if "hybrid_override_pattern" in cfg:
            return cls(**dict(_nemotron_h_fields(cfg), **overrides))
        if "layer_types" in cfg:
            return cls(**dict(_afmoe_fields(cfg), **overrides))
        if cfg.get("scoring_func", "sigmoid") != "sigmoid" or \
                cfg.get("topk_method", "noaux_tc") != "noaux_tc" or \
                cfg.get("n_group", 1) != 1 or not cfg.get("norm_topk_prob",
                                                          True):
            raise ValueError("DecoderLM routes by sigmoid scores, noaux_tc, "
                             "one group, renormalised gates")
        if not cfg.get("rope_interleave", True) or cfg.get("rope_scaling"):
            raise ValueError("DecoderLM applies interleaved RoPE with no "
                             "scaling")
        from flax.core import FrozenDict
        e = int(cfg["n_routed_experts"])
        attention = FrozenDict(
            num_heads=int(cfg["num_attention_heads"]),
            q_lora_rank=int(cfg["q_lora_rank"]),
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
            qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
            v_head_dim=int(cfg["v_head_dim"]),
            rope_theta=float(cfg["rope_theta"]))
        experts = FrozenDict(
            n_routed_experts=e,
            experts_held=int(cfg.get("experts_held", e)),
            first_expert=int(cfg.get("first_expert", 0)),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            n_shared_experts=int(cfg.get("n_shared_experts", 0)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            bias_update_rate=float(cfg.get("bias_update_rate", 1e-3)))
        kw = dict(
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=int(cfg["hidden_size"]),
            num_hidden_layers=int(cfg["num_hidden_layers"]),
            first_k_dense_replace=int(cfg.get("first_k_dense_replace", 0)),
            intermediate_size=int(cfg["intermediate_size"]),
            attention=attention, experts=experts,
            num_nextn_predict_layers=int(
                cfg.get("num_nextn_predict_layers", 0)),
            rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")),
            mtp_loss_weight=float(cfg.get("mtp_loss_weight", 0.3)))
        kw.update(overrides)
        if kw["num_nextn_predict_layers"] not in (0, 1):
            raise ValueError("DecoderLM has an MTP module of depth 0 or 1")
        return cls(**kw)

    def loss(self) -> Callable:
        """The loss to hand the estimator beside this module."""
        return functools.partial(next_token_loss,
                                 mtp_weight=self.mtp_loss_weight)

    def _mixer_block(self, kind: str, name: str):
        """A rematerialised one-mixer block (the same policy object as
        :meth:`_block`: an attention block keeps its flash results, an
        expert block its routing decision, a Mamba-2 block nothing)."""
        sizes = {"M": self.mamba, "*": self.attention, "E": self.experts}
        return nn.remat(MixerBlock, policy=_KEPT_ACROSS_REMAT)(
            kind=kind, sizes=dict(sizes[kind]), eps=self.rms_norm_eps,
            dtype=self.dtype, name=name)

    def _block(self, moe: bool, name: str, layer: Optional[int] = None):
        """A rematerialised block that keeps the flash forward kernel's two
        results across the step: the attention output, B*S*H*d_v values of
        the compute dtype, and the logsumexp, a (B*H, S, 1) column of floats
        (134 MB and 2 MB of values a block at 2 x 8192 positions, 32 heads,
        v of 128, bfloat16; the step's peak on a v5e fell by 0.11 GB), so
        the backward pass runs dQ and dK/dV on the first launch's results
        and not the forward kernel a second time; an expert layer's choice
        of experts is kept too (0.5 MB a block at 16384 rows, top-8).
        Everything else of the block is rebuilt in the backward pass as
        before: norms, projections, RoPE (so q, k and v), the router's
        scores, the expert layer, the dense FFN."""
        attention = dict(self.attention)
        if self.layer_windows is not None:
            attention["window"] = self.layer_windows[layer]
        return nn.remat(DecoderBlock, policy=_KEPT_ACROSS_REMAT)(
            attention=attention,
            ffn_width=0 if moe else self.intermediate_size,
            experts=dict(self.experts) if moe else None,
            eps=self.rms_norm_eps, sandwich_norms=self.sandwich_norms,
            dtype=self.dtype, name=name)

    @keras_call
    @nn.compact
    def __call__(self, ids, train: bool = False):
        del train                        # no dropout; the step's state is
        ids = ids.astype(jnp.int32)      # written where it is mutable
        if self.is_initializing():
            # no parameter's shape depends on the sequence's length: the
            # engine's eager init runs on a prefix, not on 8192 positions
            ids = ids[:, :INIT_POSITIONS]
        norm = functools.partial(RMSNorm, self.rms_norm_eps, self.dtype)
        embed = nn.Embed(self.vocab_size, self.hidden_size,
                         dtype=self.dtype, param_dtype=jnp.float32,
                         embedding_init=nn.initializers.normal(0.02),
                         name="embed_tokens")
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (self.hidden_size, self.vocab_size))

        def logits_of(h):
            with jax.named_scope("lm_head"):
                return jnp.dot(h, head.astype(self.dtype),
                               preferred_element_type=jnp.float32)

        x = embed(ids)
        if self.embed_scale != 1.0:
            x = x * jnp.asarray(self.embed_scale, x.dtype)
        for i in range(self.num_hidden_layers):
            if self.layer_kinds is not None:
                x = self._mixer_block(self.layer_kinds[i], f"layers_{i}")(x)
            else:
                x = self._block(i >= self.first_k_dense_replace,
                                f"layers_{i}", i)(x)
        logits = logits_of(norm(name="norm")(x))
        if not self.num_nextn_predict_layers:
            return logits, None
        with jax.named_scope("mtp"):
            nxt = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
            merged = jnp.concatenate(
                [norm(name="mtp_hnorm")(x), norm(name="mtp_enorm")(embed(nxt))],
                axis=-1)
            h = _dense(self.hidden_size, self.dtype, "mtp_eh_proj")(merged)
            if self.layer_kinds is not None:
                for i, kind in enumerate(self.mtp_kinds):
                    h = self._mixer_block(kind, f"mtp_layers_{i}")(h)
            else:
                h = self._block(True, "mtp_block")(h)
            mtp_logits = logits_of(norm(name="mtp_norm")(h))
        return logits, mtp_logits


def _afmoe_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``DecoderLM``'s fields from the ``afmoe`` family's ``config.json``
    keys (``num_dense_layers``, ``num_experts``, ``num_shared_experts``,
    ``layer_types``, ``sliding_window``, ``num_key_value_heads``,
    ``head_dim``, ``route_scale``, ``load_balance_coeff``, ``mup_enabled``,
    ...) plus this repo's ``experts_held`` / ``first_expert`` /
    ``compute_dtype``. ``layer_types`` names each layer's attention; the
    first ``num_hidden_layers`` entries are used."""
    if cfg.get("score_func", "sigmoid") != "sigmoid" or \
            not cfg.get("route_norm", True) or cfg.get("n_group", 1) != 1:
        raise ValueError("DecoderLM routes by sigmoid scores, one group, "
                         "renormalised gates")
    if cfg.get("rope_scaling") or cfg.get("tie_word_embeddings"):
        raise ValueError("DecoderLM applies RoPE with no scaling and keeps "
                         "an untied head")
    from flax.core import FrozenDict
    layers = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg["layer_types"])[:layers]
    if len(kinds) != layers or \
            set(kinds) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types names {layers} layers as "
                         f"sliding_attention or full_attention, got {kinds}")
    hidden, e = int(cfg["hidden_size"]), int(cfg["num_experts"])
    heads = int(cfg["num_attention_heads"])
    return dict(
        vocab_size=int(cfg["vocab_size"]), hidden_size=hidden,
        num_hidden_layers=layers,
        first_k_dense_replace=int(cfg.get("num_dense_layers", 0)),
        intermediate_size=int(cfg["intermediate_size"]),
        attention=FrozenDict(
            kind="gqa", num_heads=heads,
            num_kv_heads=int(cfg.get("num_key_value_heads", heads)),
            head_dim=int(cfg.get("head_dim", hidden // heads)),
            rope_theta=float(cfg["rope_theta"])),
        experts=FrozenDict(
            n_routed_experts=e,
            experts_held=int(cfg.get("experts_held", e)),
            first_expert=int(cfg.get("first_expert", 0)),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            n_shared_experts=int(cfg.get("num_shared_experts", 0)),
            routed_scaling_factor=float(cfg.get("route_scale", 1.0)),
            bias_update_rate=float(cfg.get("load_balance_coeff", 1e-3))),
        layer_windows=tuple(int(cfg["sliding_window"])
                            if kind == "sliding_attention" else None
                            for kind in kinds),
        sandwich_norms=True,
        embed_scale=math.sqrt(hidden) if cfg.get("mup_enabled") else 1.0,
        rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
        dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")))


def _nemotron_h_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``DecoderLM``'s fields from the ``nemotron_h`` family's ``config.json``
    keys (``hybrid_override_pattern``, ``mamba_num_heads``,
    ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``, ``conv_kernel``,
    ``chunk_size``, ``moe_latent_size``, ``moe_shared_expert_intermediate_size``,
    ``mtp_hybrid_override_pattern``, ...) plus this repo's share keys. The
    pattern names each layer's mixer; the first ``num_hidden_layers`` letters
    are used. ``mixer_parallel_size`` t gives every Mamba-2 mixer ``1/t`` of
    its heads and of its B/C and norm groups, every attention layer ``1/t``
    of its query heads and the kv heads they read (one where t exceeds
    them); ``mixer_parallel_rank`` says which: it picks a checkpoint's
    columns and rows, never a shape."""
    if cfg.get("n_group", 1) != 1 or not cfg.get("norm_topk_prob", True):
        raise ValueError("DecoderLM routes by sigmoid scores, one group, "
                         "renormalised gates")
    if cfg.get("mlp_hidden_act", "relu2") != "relu2" or \
            cfg.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError("DecoderLM computes this family's experts with a "
                         "squared ReLU and its Mamba-2 mixers with SiLU")
    if cfg.get("tie_word_embeddings") or cfg.get("attention_bias") or \
            cfg.get("mlp_bias") or cfg.get("mamba_proj_bias") or \
            cfg.get("use_bias") or not cfg.get("use_conv_bias", True):
        raise ValueError("DecoderLM keeps an untied head and no bias but "
                         "the convolution's")
    from flax.core import FrozenDict
    layers = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg["hybrid_override_pattern"])[:layers]
    mtp = int(cfg.get("num_nextn_predict_layers", 0))
    mtp_kinds = tuple(cfg.get("mtp_hybrid_override_pattern", "")) if mtp \
        else ()
    if len(kinds) != layers or set(kinds + mtp_kinds) - set(_MIXERS):
        raise ValueError(f"hybrid_override_pattern names {layers} layers by "
                         f"M, * or E, got {''.join(kinds)!r} (MTP "
                         f"{''.join(mtp_kinds)!r})")
    if mtp not in (0, 1) or (mtp and not mtp_kinds):
        raise ValueError("DecoderLM has an MTP module of depth 0 or 1, built "
                         "from mtp_hybrid_override_pattern")
    t = int(cfg.get("mixer_parallel_size", 1))
    rank = int(cfg.get("mixer_parallel_rank", 0))
    hidden, e = int(cfg["hidden_size"]), int(cfg["n_routed_experts"])
    heads, kv = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    m_heads, groups = int(cfg["mamba_num_heads"]), int(cfg["n_groups"])
    if not 0 <= rank < t or heads % t or m_heads % t or groups % t or \
            (kv % t and t % kv):
        raise ValueError(
            f"mixer_parallel_size {t} (rank {rank}) does not divide "
            f"{m_heads} Mamba-2 heads in {groups} groups and {heads} query "
            f"heads over {kv} kv heads into whole shares")
    eps = float(cfg.get("layer_norm_epsilon", cfg.get("norm_eps", 1e-5)))
    return dict(
        vocab_size=int(cfg["vocab_size"]), hidden_size=hidden,
        num_hidden_layers=layers, first_k_dense_replace=0,
        intermediate_size=int(cfg.get("intermediate_size", 0)),
        layer_kinds=kinds, mtp_kinds=mtp_kinds,
        num_nextn_predict_layers=mtp,
        mamba=FrozenDict(
            num_heads=m_heads // t, head_dim=int(cfg["mamba_head_dim"]),
            n_groups=groups // t, state_size=int(cfg["ssm_state_size"]),
            conv_kernel=int(cfg["conv_kernel"]),
            chunk_size=int(cfg["chunk_size"]), eps=eps),
        attention=FrozenDict(
            num_heads=heads // t, num_kv_heads=max(kv // t, 1),
            head_dim=int(cfg.get("head_dim", hidden // heads)),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            eps=eps, gated=False, qk_norm=False),
        experts=FrozenDict(
            n_routed_experts=e,
            experts_held=int(cfg.get("experts_held", e)),
            first_expert=int(cfg.get("first_expert", 0)),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            n_shared_experts=int(cfg.get("n_shared_experts", 0)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor",
                                                1.0)),
            bias_update_rate=float(cfg.get("bias_update_rate", 1e-3)),
            latent_size=int(cfg.get("moe_latent_size") or 0),
            activation="relu2", gated=False,
            shared_width=int(cfg.get("moe_shared_expert_intermediate_size",
                                     0))),
        rms_norm_eps=eps,
        dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")),
        mtp_loss_weight=float(cfg.get("mtp_loss_weight", 0.3)))


def _shifted_nll(logits, ids, shift: int):
    """Per sequence, the mean over the positions that have a label of
    ``-log softmax(logits[:, t])[ids[:, t + shift]]``."""
    s = ids.shape[1]
    labels = jnp.pad(ids[:, shift:], ((0, 0), (0, shift)))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    valid = (jnp.arange(s) < s - shift).astype(jnp.float32)
    return jnp.sum(nll * valid, axis=-1) / (s - shift)


def next_token_loss(ids, preds, mtp_weight: float = 0.3):
    """``CE(main, tok_{t+1}) + mtp_weight * CE(MTP, tok_{t+2})`` for each
    sequence (the engine takes the mean over the batch); the labels are the
    input ids, shifted here, on the device."""
    logits, mtp_logits = preds
    ids = ids.astype(jnp.int32)
    loss = _shifted_nll(logits, ids, 1)
    if mtp_logits is not None:
        loss = loss + mtp_weight * _shifted_nll(mtp_logits, ids, 2)
    return loss


def moe_counters(extra_vars: Dict[str, Any]) -> Dict[str, float]:
    """The expert layers' counters from a step's outputs (the engine's
    ``extra_vars``), one host fetch for all layers: ``moe_local_rows`` (the
    token-choices routed to the experts held, a step, summed over the
    layers), ``moe_rows_max_over_mean`` (the last step's imbalance over the
    experts held, the worst layer), ``moe_dropped_rows`` (must read 0),
    ``moe_rows_moved_over_routed`` (rows the gathers fetched over the rows
    routed here, over the steps counted), ``moe_steps``."""
    stats = jax.device_get(extra_vars.get(MOE_STATS, {}))
    layers = [v["mlp"] for v in stats.values() if "mlp" in v]
    if not layers:
        return {}
    steps = max(int(l["steps"]) for l in layers)
    rows = float(sum(float(l["rows_total"]) for l in layers))
    moved = float(sum(float(l["rows_moved"]) for l in layers))
    out = {
        "moe_steps": steps,
        "moe_rows_total": rows,
        "moe_local_rows": rows / max(steps, 1),
        "moe_rows_max_over_mean": float(max(float(l["rows_max_over_mean"])
                                            for l in layers)),
        "moe_dropped_rows": int(sum(int(l["dropped_rows"]) for l in layers)),
        "moe_rows_moved_over_routed": moved / max(rows, 1.0),
    }
    for name in ("moe_local_rows", "moe_rows_max_over_mean",
                 "moe_dropped_rows", "moe_rows_moved_over_routed"):
        _REGISTRY.gauge(f"zoo_{name}", _GAUGE_DOC[name]).set(out[name])
    return out
