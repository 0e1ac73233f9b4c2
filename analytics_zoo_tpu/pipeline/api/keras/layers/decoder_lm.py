"""A decoder language model of six published families, chosen by the
``model_type`` of the ``config.json`` it is built from, or by its keys where
it names none (:meth:`DecoderLM.from_config`):

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
  from ``mtp_hybrid_override_pattern``;
* the ``granitemoehybrid`` family (by its ``model_type``): every block is a
  mixer by ``layer_types`` (a Mamba-2 mixer or plain grouped-query
  attention with no position encoding and the softmax at
  ``attention_multiplier``) and then a SwiGLU MLP, each behind its norm and
  each branch times ``residual_multiplier``; the embedding times
  ``embedding_multiplier``, the logits over ``logits_scaling`` (the muP
  scalars); the head tied to the embedding; no expert layer, no MTP module;
* the ``sdar_moe`` family: a Qwen3-MoE-style decoder (plain grouped-query
  attention with q/k norms and rotate-half RoPE on every layer, every block
  an expert layer routed by softmax, no shared expert) trained as a
  block-diffusion language model: a step draws noise, runs a noisy copy and
  the clean copy of each sequence side by side under the block-diffusion
  mask, and scores the masked positions of the noisy copy.

A family's reader turns its config into a per-layer plan, one :class:`Layer`
a block (its sublayers with their sizes and norms, the residual scale, the
remat policy), and one :class:`Block` builds every entry.

Trained through the estimator like any other module::

    model = DecoderLM.from_config(cfg)
    est = TPUEstimator(model, loss=model.loss(), optimizer=AdamWeightDecay(...))
    est.fit({"x": ids, "y": ids}, epochs=..., batch_size=...)

``ids`` are (batch, seq) token ids; the labels are the same ids, shifted on
the device: by one for the main head, by two for the MTP head. Attention is
causal over the whole sequence (no cross-document mask, as the family trains).
The ``sdar_moe`` family takes the same ids and labels, unshifted
(:func:`block_diffusion_loss`).

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

* ``granitemoehybrid``: ``x = Emb(ids) m_emb``; a block ``x = x + m_res
  Mixer(RMSNorm(x))``; ``x = x + m_res (silu(h W_gate) * h W_up) W_down``
  with ``h = RMSNorm(x)``; the mixer ``nemotron_h``'s ``M`` or ``*`` with
  ``softmax(q k^T m_att) v``; ``logits = RMSNorm(x) Emb^T / m_logits``.

* ``sdar_moe`` (a sequence x_0 of L tokens, blocks ``blk(i) = (i mod L) //
  B`` of ``block_length`` B): noise ``t_b ~ U(0, 1)`` a block, ``p_b = (1 -
  eps) t_b + eps``, ``m_i ~ Bernoulli(p_blk(i))``, ``x_t[i] = MASK if m_i
  else x_0[i]`` (:func:`block_diffusion_noise`: a pure function of the
  module's ``noise_seed``, kept in its state, and its own count of training
  steps); ``z = [x_t | x_0]`` at positions ``[0..L-1 | 0..L-1]``, ``x =
  Emb(z)`` (MASK is one more row); a block ``x = x + Attn(RMSNorm(x))``, ``x = x + MoE(RMSNorm(
  x))``; ``q = x W_q``, ``k = x W_k``, ``v = x W_v``, RMSNorm over each
  head's width on q and k, then RoPE; ``softmax(q k^T / sqrt(d) + M_bd) v``
  with the block-diffusion mask (``ops/attention.py
  block_diffusion_mask``: a noisy query sees its own noisy block and the
  clean blocks before it, a clean one the clean blocks up to its own); the
  expert layer's gates ``softmax(r)[C] / sum_C softmax(r)`` over its top-k
  logits ``C``; ``logits = RMSNorm(x[:, :L]) W_head`` (the noisy half);
  ``loss = (1/L) sum_i m_i / p_blk(i) CE(logits_i, x_0[i])``.

* ``KeyeVL2`` (its language model): ``sdar_moe``'s block trained by
  next-token prediction, its attention learned sparse attention (DeepSeek
  Sparse Attention's sparse stage): q and k normed, then MRoPE; a
  lightning indexer on the detached input, ``I[t, s] = sum_j w_tj
  ReLU(q^I_tj . k^I_s)``, picks each query's top-k keys S_t
  (``ops/sparse_attention.py``); ``softmax_{s in S_t}(q k^T / sqrt(d)) v``
  through the selected-key flash kernel; ``L_I = mean_t KL(p_t ||
  softmax_{S_t} I_t)``, p_t the attention's probabilities over S_t meaned
  over the heads and not differentiated; ``loss = CE + lambda mean_layers
  L_I``.

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

import contextlib
import functools
import math
import threading
from typing import Any, Callable, Dict, NamedTuple, Optional

import flax.linen as nn
from flax.core import FrozenDict
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.obs.registry import REGISTRY as _REGISTRY
from analytics_zoo_tpu.ops.attention import (
    BLOCK_DIFFUSION, FLASH_RESIDUAL_NAMES, SA_ENTRIES, SELECTED,
    flash_attention, selection_entries)
from analytics_zoo_tpu.ops.sparse_attention import (
    INDEXER_GRAD_NAME, INDEXER_SCOPE, SELECTION_NAME, indexer_kl,
    indexer_select, selection_key_bins)
from analytics_zoo_tpu.ops.ssm import causal_conv1d, ssd_scan
from analytics_zoo_tpu.parallel.expert_parallel import (
    ROUTER_CHOICE_NAME, expert_load, held_experts_ffn, noaux_bias_update,
    route_noaux_tc, route_softmax_topk)
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
NOISE_STATE = "noise_state"        # the block-diffusion noise's seed, steps
SA_STATS = "sa_stats"              # the selected-key layers' last step
SA_KEY_BINS = 128                  # spans of the keys a query's selection
                                   # is counted in (sa_stats)
# a SwiGLU's first products (gate, up), as ``checkpoint_name`` marks them: an
# identity wherever no remat policy keeps them
FFN_PRODUCT_NAMES = ("ffn_gate", "ffn_up")


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


def _rope_angles(x, theta: float, positions=None, sections=None):
    """cos and sin of ``pos * theta**(-2i/d)``, (1, seq, 1, d/2), float32;
    ``pos`` the sequence's index, or ``positions`` (seq,); with
    ``sections`` (MRoPE) ``positions`` is (len(sections), seq) and pair i
    takes the row of the section it falls in, the sections in turn over
    the d/2 pairs."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pos = jnp.arange(x.shape[1], dtype=jnp.float32) if positions is None \
        else positions.astype(jnp.float32)
    if sections:
        if sum(sections) != d // 2 or pos.shape != (len(sections),
                                                    x.shape[1]):
            raise ValueError(f"MRoPE sections {tuple(sections)} over {d // 2} "
                             f"pairs, positions {pos.shape}")
        ang = pos[np.repeat(np.arange(len(sections)), sections)].T * inv
    else:
        ang = pos[:, None] * inv[None, :]
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


def rope_half(x, theta: float, positions=None, sections=None):
    """Rotary position embedding over the pairs ``(x[i], x[i + d/2])`` of the
    last axis (rotate-half), angle ``pos * theta**(-2i/d)`` with ``pos`` the
    index in the sequence or ``positions`` (seq,). With ``sections``
    (MRoPE, Qwen2-VL's chunked form) ``positions`` is (3, seq), the
    temporal, height and width positions, and frequency pair i takes the
    first's for i < sections[0], the second's for the next sections[1], the
    third's for the rest; with the three equal it is plain rotate-half RoPE.
    x: (batch, seq, heads, d); computed in float32."""
    d = x.shape[-1]
    cos, sin = _rope_angles(x, theta, positions, sections)
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
    to the flash kernel with their own ``num_kv_heads``. ``sm_scale`` is the
    softmax's scale of the scores (by default ``1 / sqrt(head_dim)``).
    ``block_diffusion`` B reads the sequence as ``[x_t | x_0]``, two copies
    of a sequence: RoPE at positions ``t mod (seq / 2)`` and the
    block-diffusion mask for blocks of B (under ``attn.bd``).

    ``indexer_heads`` makes the layer learned sparse attention (DSA's sparse
    stage, ``ops/sparse_attention.py``): RoPE on q and k (MRoPE with
    ``mrope_section``, the three position streams a text's index), a
    lightning indexer of that many heads of ``indexer_head_dim`` on the
    detached input (under ``sa.indexer``) picks each query's
    ``indexer_topk`` keys, the flash kernel attends to them alone (under
    ``attn.sa``), and the layer returns ``(out, L_I)``, the indexer's KL
    loss of each sequence; ``indexer_rows`` is the chunk of queries the
    indexer works on, and the kernels' tile. Its last step's numbers go to
    ``sa_stats``."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    eps: float
    window: Optional[int] = None
    gated: bool = True
    qk_norm: bool = True
    sm_scale: Optional[float] = None
    block_diffusion: int = 0
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    indexer_topk: int = 0
    indexer_rows: int = 512
    mrope_section: Optional[tuple] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        norm = functools.partial(RMSNorm, self.eps, self.dtype)
        if self.indexer_heads:
            indexer = self._indexer(x)
        with jax.named_scope("attn.gqa"):
            q = _dense(h * d, self.dtype, "q_proj")(x).reshape(b, s, h, d)
            k = _dense(hk * d, self.dtype, "k_proj")(x).reshape(b, s, hk, d)
            v = _dense(hk * d, self.dtype, "v_proj")(x).reshape(b, s, hk, d)
            if self.gated:
                gate = _dense(h * d, self.dtype, "gate_proj")(x)
            if self.qk_norm:
                q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
            if self.indexer_heads:
                pos = jnp.broadcast_to(jnp.arange(s), (3, s)) \
                    if self.mrope_section else None
                q, k = (rope_half(t, self.rope_theta, pos, self.mrope_section)
                        for t in (q, k))
                with jax.named_scope("attn.sa"):
                    out, lse = self._selected(q, k, v, indexer)
            elif self.block_diffusion:
                pos = jnp.arange(s) % (s // 2)
                q, k = (rope_half(t, self.rope_theta, pos) for t in (q, k))
                with jax.named_scope("attn.bd"):
                    out = flash_attention(q, k, v, sm_scale=self.sm_scale,
                                          mask=BLOCK_DIFFUSION,
                                          block=self.block_diffusion)
            else:
                if self.window is not None:
                    q, k = (rope_half(t, self.rope_theta) for t in (q, k))
                with jax.named_scope("attn.global" if self.window is None
                                     else "attn.window"):
                    out = flash_attention(q, k, v, causal=True,
                                          window=self.window,
                                          sm_scale=self.sm_scale)
            out = out.reshape(b, s, h * d)
            if self.gated:
                out = out * jax.nn.sigmoid(gate)
            y = _dense(hidden, self.dtype, "o_proj")(out)
        if not self.indexer_heads:
            return y
        return y, self._indexer_loss(q, k, lse, indexer)

    def _rows(self, s):
        return min(self.indexer_rows, s)

    def _indexer(self, x):
        """The indexer's ``(q^I, w, k^I, packed selection)`` from the
        detached input (the selection None while the module is made)."""
        b, s, _ = x.shape
        hi, di = self.indexer_heads, self.indexer_head_dim
        with jax.named_scope(INDEXER_SCOPE):
            x = jax.lax.stop_gradient(x)
            q_i = _dense(hi * di, self.dtype, "indexer_q_proj")(x).reshape(
                b, s, hi, di)
            k_i = nn.LayerNorm(epsilon=self.eps, dtype=self.dtype,
                               param_dtype=jnp.float32,
                               name="indexer_k_norm")(
                _dense(di, self.dtype, "indexer_k_proj")(x))
            w = _dense(hi, self.dtype, "indexer_weights_proj")(x).astype(
                jnp.float32) * (hi * di) ** -0.5
            q_i = rope_half(q_i, self.rope_theta)
            k_i = rope_half(k_i[:, :, None], self.rope_theta)[:, :, 0]
            if self.is_initializing():
                # no parameter's shape depends on the selection: the
                # engine's eager init does not compile it for its prefix
                return q_i, w, k_i, None
            bits = checkpoint_name(
                indexer_select(q_i, w, k_i, self.indexer_topk,
                               self._rows(s)), SELECTION_NAME)
            return q_i, w, k_i, bits

    def _selected(self, q, k, v, indexer):
        """The attention to each query's selected keys and its base-2
        logsumexp (zeros while the module is made)."""
        bits = indexer[-1]
        if bits is None:
            b, s, h, d = q.shape
            return jnp.zeros_like(q), jnp.zeros((b, h, s), jnp.float32)
        return flash_attention(q, k, v, sm_scale=self.sm_scale, mask=SELECTED,
                               selection=bits, block=self._rows(q.shape[1]),
                               return_lse=True)

    def _indexer_loss(self, q, k, lse, indexer):
        """``L_I`` of each sequence (batch,), and the layer's last step in
        ``sa_stats``: its mean ``L_I`` (``kl``), the entries the selected-key
        walk computed and the selected ones, a head and a forward pass, and
        how many keys each query chose in each of ``SA_KEY_BINS`` spans of
        equal width over the keys (``key_bins`` (L, spans), summed over the
        batch)."""
        q_i, w, k_i, bits = indexer
        b, s = q.shape[:2]
        stats = {name: self.variable(SA_STATS, name, jnp.zeros, (), dt)
                 for name, dt in (("kl", jnp.float32), ("steps", jnp.int32),
                                  ("entries_computed", jnp.float32),
                                  ("entries_needed", jnp.float32))}
        bins = min(SA_KEY_BINS, s)
        stats["key_bins"] = self.variable(SA_STATS, "key_bins", jnp.zeros,
                                          (s, bins), jnp.int32)
        if bits is None:
            return jnp.zeros((b,), jnp.float32)
        rows = self._rows(s)
        loss = indexer_kl(q_i, w, k_i, q, k, lse, bits,
                          sm_scale=self.sm_scale or self.head_dim ** -0.5,
                          rows=rows)
        if self.is_mutable_collection(SA_STATS):
            with jax.named_scope(INDEXER_SCOPE):
                computed, needed = selection_entries(bits, rows)
                stats["key_bins"].value = jnp.sum(
                    selection_key_bins(bits, rows, bins), axis=0)
                stats["kl"].value = jax.lax.stop_gradient(jnp.mean(loss))
                stats["steps"].value += 1
                stats["entries_computed"].value = computed
                stats["entries_needed"].value = needed / b
        return loss


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
        gate_name, up_name = FFN_PRODUCT_NAMES
        if self.gated:
            gate = checkpoint_name(
                _dense(self.width, self.dtype, "gate_proj")(x), gate_name)
        up = checkpoint_name(_dense(self.width, self.dtype, "up_proj")(x),
                             up_name)
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
    (else ``moe_intermediate_size * n_shared_experts``). ``scoring``
    ``"softmax"`` routes by :func:`route_softmax_topk` (no correction bias,
    no ``router_state`` leaf) where ``"sigmoid"`` routes by
    :func:`route_noaux_tc`."""
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
    scoring: str = "sigmoid"

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        e, held, f = (self.n_routed_experts, self.experts_held,
                      self.moe_intermediate_size)
        d = self.latent_size or hidden       # the routed experts' rows
        flat = x.reshape(b * s, hidden)
        router = self.param("gate", nn.initializers.normal(0.02), (hidden, e))
        bias = None if self.scoring == "softmax" else self.variable(
            ROUTER_STATE, "e_score_correction_bias", jnp.zeros, (e,),
            jnp.float32)
        init = nn.initializers.normal(0.02)
        w_gate = self.param("experts_gate_proj", init, (held, d, f)) \
            if self.gated else None
        w_up = self.param("experts_up_proj", init, (held, d, f))
        w_down = self.param("experts_down_proj", init, (held, f, d))
        with jax.named_scope("moe.router"):
            if bias is None:
                idx, gates = route_softmax_topk(
                    flat, router, top_k=self.num_experts_per_tok)
            else:
                idx, gates = route_noaux_tc(
                    flat, router, bias.value,
                    top_k=self.num_experts_per_tok,
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
        """What a training step leaves behind: the bias (if any) moved
        against the step's load, the layer's counters (``load``: the step's
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
            if bias is not None and self.is_mutable_collection(ROUTER_STATE):
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


_REMAT_KEPT_BYTES = _REGISTRY.gauge(
    "zoo_remat_kept_bytes",
    "bytes a training step keeps across its blocks' rematerialisation under "
    "each saved name, summed over the blocks of the last model call traced "
    "for a gradient", labelnames=("name",))
_TALLY = threading.local()         # the model call being traced: name -> bytes


class _KeepNames:
    """``save_only_these_names(*names)`` as a policy object that adds the
    bytes of each value it keeps to the tally of the model call being traced
    (``_remat_tally``). A remat's partial evaluation asks it once for each
    equation of the block's forward pass, while the block is traced."""

    def __init__(self, *names):
        self.names = names
        self._keeps = jax.checkpoint_policies.save_only_these_names(*names)

    def __call__(self, prim, *avals, **params):
        keep = self._keeps(prim, *avals, **params)
        tally = getattr(_TALLY, "kept", None)
        if keep and tally is not None:
            tally[params["name"]] = tally.get(params["name"], 0) + \
                avals[0].size * avals[0].dtype.itemsize
        return keep


# Two policies, each ONE object: blocks whose remat parameters are the same
# object share one lowered function, as blocks under a plain remat do. Each
# family's reader gives its blocks one of them. Both keep the flash forward
# kernel's two results, the attention output (B*S*H*d_v values of the compute
# dtype) and its logsumexp ((B*H, S, 1) floats): 134 MB and 2 MB a block at 2
# x 8192 positions, 32 heads, v of 128, bfloat16, so the backward pass runs
# dQ and dK/dV on the first launch's results and not the forward kernel a
# second time. Both keep an expert layer's routing decision ((tokens, top_k)
# int32, 0.5 MB a block at 16384 rows, top-8: the backward pass selects
# nothing). Everything else of a block is rebuilt in the backward pass.
_KEPT_ACROSS_REMAT = _KeepNames(*FLASH_RESIDUAL_NAMES, ROUTER_CHOICE_NAME)
# The hybrid families' blocks (nemotron_h, granitemoehybrid) keep their
# SwiGLU's first products too, so the backward pass does not run gate_proj
# and up_proj again (Granite: 2 x 134 MB a block at 8192 tokens; Nemotron's
# shared experts: 88 MB an E block). The other families' blocks do not:
# their steps use 86-91% of a v5e's memory, and their only SwiGLUs (a dense
# first layer's FFN, the shared experts) would give back ~1% of the step.
# Revisit once their memory is freed.
_KEPT_WITH_FFN_PRODUCTS = _KeepNames(
    *FLASH_RESIDUAL_NAMES, ROUTER_CHOICE_NAME, *FFN_PRODUCT_NAMES)
# The learned-sparse-attention family's blocks keep the packed selection (32
# MiB a block at 16384 positions) and the indexer's gradients, made beside
# its loss (35 MiB there), so the backward pass neither selects
# nor runs the loss's chunks again.
_KEPT_WITH_SELECTION = _KeepNames(
    *FLASH_RESIDUAL_NAMES, ROUTER_CHOICE_NAME, SELECTION_NAME,
    INDEXER_GRAD_NAME)


@contextlib.contextmanager
def _remat_tally():
    """Tallies what the blocks' policies keep while one model call is
    traced. A call traced for a gradient (one in which a policy kept
    something) sets ``zoo_remat_kept_bytes`` for every name either policy
    keeps, 0 where none was kept; a forward-only trace leaves it alone."""
    _TALLY.kept = tally = {}
    try:
        yield
    finally:
        _TALLY.kept = None
    if tally:
        for name in dict.fromkeys(_KEPT_WITH_FFN_PRODUCTS.names
                                  + _KEPT_WITH_SELECTION.names):
            _REMAT_KEPT_BYTES.labels(name=name).set(tally.get(name, 0))


# a block's sublayers by kind: the module, and the name it takes in the tree
# (the expert layer's is the one the counters read)
_SUBLAYERS = {"mla": (MLAttention, "self_attn"),
              "gqa": (GQAttention, "self_attn"),
              "mamba2": (Mamba2Mixer, "mixer"),
              "experts": (SparseExperts, "mlp"),
              "swiglu": (SwiGLU, "mlp")}


class Sublayer(NamedTuple):
    """One sublayer of a block: ``kind`` a key of ``_SUBLAYERS``, ``sizes``
    that module's fields (a FrozenDict), ``norm`` the name of the RMSNorm on
    its input, ``post_norm`` that of one on its output (sandwich norms),
    ``scope`` a named scope around the module."""
    kind: str
    sizes: Any
    norm: str
    post_norm: Optional[str] = None
    scope: Optional[str] = None


class Layer(NamedTuple):
    """One entry of a ``DecoderLM``'s plan: its sublayers (:class:`Sublayer`)
    in order, the remat ``policy`` object its block runs under, and the
    ``residual_scale`` of every branch."""
    sublayers: tuple
    policy: _KeepNames
    residual_scale: float = 1.0


def _two_sublayers(mixer, ffn, policy: _KeepNames, sandwich: bool = False,
                   residual_scale: float = 1.0,
                   ffn_scope: Optional[str] = None) -> Layer:
    """A mixer then a feed-forward, each ``(kind, sizes)``, behind the
    pre-norms ``input_layernorm`` and ``post_attention_layernorm``, or behind
    sandwich norms: ``input_layernorm`` and ``pre_mlp_layernorm`` before,
    ``post_attention_layernorm`` and ``post_mlp_layernorm`` after."""
    if sandwich:
        return Layer((Sublayer(*mixer, "input_layernorm",
                               "post_attention_layernorm"),
                      Sublayer(*ffn, "pre_mlp_layernorm", "post_mlp_layernorm",
                               ffn_scope)), policy, residual_scale)
    return Layer((Sublayer(*mixer, "input_layernorm"),
                  Sublayer(*ffn, "post_attention_layernorm", None, ffn_scope)),
                 policy, residual_scale)


class Block(nn.Module):
    """A plan's :class:`Layer`: for each sublayer in turn ``x = x + s
    N_post(Sub(N(x)))`` (``N_post`` where it has one), ``s`` the residual
    scale, applied in float32 where it is not 1. ``window`` is the
    attention's (``DecoderLM.layer_windows``; None: no window). A sublayer
    that returns ``(y, aux)`` adds an auxiliary loss (a learned-sparse
    attention's ``L_I``); the block then returns ``(x, aux)``, the sum of
    its sublayers'."""
    layer: Layer
    eps: float
    window: Optional[int] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(RMSNorm, self.eps, self.dtype)
        scale = self.layer.residual_scale
        aux = None
        for sub in self.layer.sublayers:
            module, name = _SUBLAYERS[sub.kind]
            sizes = dict(sub.sizes)
            if self.window is not None and module is GQAttention:
                sizes["window"] = self.window
            h = norm(name=sub.norm)(x)
            with jax.named_scope(sub.scope) if sub.scope else \
                    contextlib.nullcontext():
                y = module(dtype=self.dtype, name=name, **sizes)(h)
            if isinstance(y, tuple):
                y, more = y
                aux = more if aux is None else aux + more
            if sub.post_norm:
                y = norm(name=sub.post_norm)(y)
            if scale != 1.0:
                y = (y.astype(jnp.float32) * scale).astype(self.dtype)
            x = x + y
        return x if aux is None else (x, aux)


class DecoderLM(nn.Module):
    """ids (batch, seq) -> ``(logits, mtp_logits)``, both (batch, seq, vocab)
    in float32. ``logits[:, t]`` scores ``tok_{t+1}``; ``mtp_logits[:, t]``
    scores ``tok_{t+2}`` (its last position, whose input token lies beyond
    the sequence, is fed token 0 and left out of the loss). With no MTP
    module ``mtp_logits`` is None; where the blocks return an auxiliary
    loss (learned sparse attention's ``L_I``) it stands in its place,
    (batch,), the mean over the blocks. Trained by block diffusion
    (``diffusion_block``) it returns ``(logits, m / p)`` instead: the noisy
    copy's logits, which score each position's own token, and the loss's
    weight of each position (:func:`block_diffusion_loss`).

    ``layers`` is the plan, one :class:`Layer` a block (``layers_{i}`` in the
    tree); ``mtp_layers`` the MTP module's, ``(name, Layer)`` pairs (empty:
    no MTP module). A family's reader (``_FIELDS``) writes both."""
    vocab_size: int
    hidden_size: int
    layers: tuple
    mtp_layers: tuple = ()
    # each layer's attention window (None: a global layer; None overall: no
    # layer has one)
    layer_windows: Optional[tuple] = None
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    mtp_loss_weight: float = 0.3
    # the embedding's scale, the logits' divisor; the head tied to the
    # embedding (no ``lm_head`` leaf: one leaf takes both gradients)
    embed_scale: float = 1.0
    logits_scale: float = 1.0
    tie_word_embeddings: bool = False
    # trained as a block-diffusion LM over blocks of this many tokens (0:
    # next-token prediction), the noise's floor and seed (the ids' embedding
    # has one more row, the MASK token's)
    diffusion_block: int = 0
    noise_eps: float = 1e-3
    noise_seed: int = 0
    # the weight of the blocks' auxiliary loss in the loss (None: they
    # return none)
    indexer_loss_weight: Optional[float] = None

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "DecoderLM":
        """From a ``config.json`` of one of the six families, chosen by its
        ``model_type`` (``MODEL_TYPES``); a config that names none is read
        by its keys (``hybrid_override_pattern``: ``nemotron_h``;
        ``layer_types``: ``afmoe``; else the DeepSeek-V3 family). Beside the
        published keys: ``experts_held`` / ``first_expert`` (this rank's
        share of each layer's ``n_routed_experts``; by default all of them),
        ``bias_update_rate`` (gamma), ``mtp_loss_weight`` (lambda),
        ``compute_dtype``; for the ``nemotron_h`` family also
        ``mixer_parallel_size`` / ``mixer_parallel_rank`` (this rank's share
        of each Mamba-2 mixer's and attention layer's heads; by default the
        whole layer); for ``sdar_moe`` ``block_length``, ``noise_eps`` and
        ``noise_seed``; for ``KeyeVL2`` ``indexer_loss_weight``."""
        family = cfg.get("model_type")
        if family is None:
            family = "nemotron_h" if "hybrid_override_pattern" in cfg else \
                "afmoe" if "layer_types" in cfg else "deepseek_v3"
        for name, types in MODEL_TYPES.items():
            if family in types:
                return cls(**_FIELDS[name](cfg))
        raise ValueError(
            f"model_type {family!r} is none of DecoderLM's six families: "
            + "; ".join(f"{name} ({', '.join(types)})"
                        for name, types in MODEL_TYPES.items()))

    def loss(self) -> Callable:
        """The loss to hand the estimator beside this module."""
        if self.diffusion_block:
            return block_diffusion_loss
        if self.indexer_loss_weight is not None:
            return functools.partial(sparse_attention_loss,
                                     indexer_weight=self.indexer_loss_weight)
        return functools.partial(next_token_loss,
                                 mtp_weight=self.mtp_loss_weight)

    def _diffusion_input(self, ids):
        """``([x_t | x_0] (batch, 2L), m / p (batch, L) float32)``: the noise
        drawn with the key ``fold_in(PRNGKey(seed), n)``, n the training
        steps this module has taken; both are its ``noise_state`` (the seed
        set from ``noise_seed`` at init, so that it is the step's data and
        not a constant of its program: every seed runs one compiled step),
        and a training step moves n on. Masked positions take the MASK row,
        ``vocab_size``. The engine's eager init draws none."""
        steps = self.variable(NOISE_STATE, "steps", jnp.zeros, (), jnp.int32)
        seed = self.variable(NOISE_STATE, "seed", jnp.asarray,
                             self.noise_seed, jnp.int32)
        if self.is_initializing():
            masked = jnp.zeros(ids.shape, bool)
            p = jnp.ones(ids.shape, jnp.float32)
        else:
            with jax.named_scope("bd.noise"):
                key = jax.random.fold_in(jax.random.PRNGKey(seed.value),
                                         steps.value)
                masked, p = block_diffusion_noise(
                    key, ids.shape[0], ids.shape[1], self.diffusion_block,
                    self.noise_eps)
                if self.is_mutable_collection(NOISE_STATE):
                    steps.value += 1
        noisy = jnp.where(masked, self.vocab_size, ids)
        return jnp.concatenate([noisy, ids], axis=1), \
            masked.astype(jnp.float32) / p

    def _block(self, layer: Layer, name: str, window: Optional[int] = None):
        """``layer``'s block, rematerialised under its policy object."""
        return nn.remat(Block, policy=layer.policy)(
            layer=layer, eps=self.rms_norm_eps, window=window,
            dtype=self.dtype, name=name)

    @keras_call
    @nn.compact
    def __call__(self, ids, train: bool = False):
        # no dropout; the step's state is written where it is mutable
        del train
        with _remat_tally():
            return self._forward(ids)

    def _forward(self, ids):
        ids = ids.astype(jnp.int32)
        if self.is_initializing():
            # no parameter's shape depends on the sequence's length: the
            # engine's eager init runs on a prefix, not on 8192 positions
            ids = ids[:, :INIT_POSITIONS]
        length = ids.shape[1]
        if self.diffusion_block:
            ids, weight = self._diffusion_input(ids)
        norm = functools.partial(RMSNorm, self.rms_norm_eps, self.dtype)
        embed = nn.Embed(self.vocab_size + bool(self.diffusion_block),
                         self.hidden_size,
                         dtype=self.dtype, param_dtype=jnp.float32,
                         embedding_init=nn.initializers.normal(0.02),
                         name="embed_tokens")
        if not self.tie_word_embeddings:
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (self.hidden_size, self.vocab_size))

        def logits_of(h):
            with jax.named_scope("lm_head"):
                if not self.tie_word_embeddings:
                    out = jnp.dot(h, head.astype(self.dtype),
                                  preferred_element_type=jnp.float32)
                else:
                    out = jnp.einsum(
                        "bsh,vh->bsv", h, embed.embedding.astype(self.dtype),
                        preferred_element_type=jnp.float32)
                if self.logits_scale != 1.0:
                    out = out / self.logits_scale
                return out

        if self.tie_word_embeddings:
            with jax.named_scope("embed.tied"):
                x = embed(ids)
        else:
            x = embed(ids)
        if self.embed_scale != 1.0:
            x = x * jnp.asarray(self.embed_scale, x.dtype)
        windows = self.layer_windows or (None,) * len(self.layers)
        aux = []
        for i, (layer, window) in enumerate(zip(self.layers, windows)):
            x = self._block(layer, f"layers_{i}", window)(x)
            if isinstance(x, tuple):
                x, more = x
                aux.append(more)
        if aux:
            # the blocks' auxiliary loss beside the logits, in MTP's place
            return logits_of(norm(name="norm")(x)), sum(aux) / len(aux)
        if self.diffusion_block:
            # the noisy half's logits; the loss's weights beside them
            return logits_of(norm(name="norm")(x[:, :length])), weight
        logits = logits_of(norm(name="norm")(x))
        if not self.mtp_layers:
            return logits, None
        with jax.named_scope("mtp"):
            nxt = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
            merged = jnp.concatenate(
                [norm(name="mtp_hnorm")(x), norm(name="mtp_enorm")(embed(nxt))],
                axis=-1)
            h = _dense(self.hidden_size, self.dtype, "mtp_eh_proj")(merged)
            for name, layer in self.mtp_layers:
                h = self._block(layer, name)(h)
            mtp_logits = logits_of(norm(name="mtp_norm")(h))
        return logits, mtp_logits


def _deepseek_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``DecoderLM``'s fields from the DeepSeek-V3 family's ``config.json``
    keys (``first_k_dense_replace``, ``n_routed_experts``, ``q_lora_rank``,
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``, ``num_nextn_predict_layers``, ...) plus this repo's
    ``experts_held`` / ``first_expert`` / ``bias_update_rate`` /
    ``mtp_loss_weight`` / ``compute_dtype``: ``first_k_dense_replace`` dense
    blocks, then expert blocks, and an MTP module of one expert block where
    ``num_nextn_predict_layers`` is 1."""
    if cfg.get("scoring_func", "sigmoid") != "sigmoid" or \
            cfg.get("topk_method", "noaux_tc") != "noaux_tc" or \
            cfg.get("n_group", 1) != 1 or not cfg.get("norm_topk_prob", True):
        raise ValueError("DecoderLM routes by sigmoid scores, noaux_tc, "
                         "one group, renormalised gates")
    if not cfg.get("rope_interleave", True) or cfg.get("rope_scaling"):
        raise ValueError("DecoderLM applies interleaved RoPE with no "
                         "scaling")
    mtp = int(cfg.get("num_nextn_predict_layers", 0))
    if mtp not in (0, 1):
        raise ValueError("DecoderLM has an MTP module of depth 0 or 1")
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    e = int(cfg["n_routed_experts"])
    attention = ("mla", FrozenDict(
        num_heads=int(cfg["num_attention_heads"]),
        q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]), eps=eps))
    experts = ("experts", FrozenDict(
        n_routed_experts=e,
        experts_held=int(cfg.get("experts_held", e)),
        first_expert=int(cfg.get("first_expert", 0)),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        n_shared_experts=int(cfg.get("n_shared_experts", 0)),
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        bias_update_rate=float(cfg.get("bias_update_rate", 1e-3))))
    dense, moe = (_two_sublayers(attention, ffn, _KEPT_ACROSS_REMAT) for ffn
                  in (("swiglu", FrozenDict(width=int(
                      cfg["intermediate_size"]))), experts))
    k = int(cfg.get("first_k_dense_replace", 0))
    return dict(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        layers=tuple(dense if i < k else moe
                     for i in range(int(cfg["num_hidden_layers"]))),
        mtp_layers=(("mtp_block", moe),) if mtp else (),
        rms_norm_eps=eps,
        dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")),
        mtp_loss_weight=float(cfg.get("mtp_loss_weight", 0.3)))


def _afmoe_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``DecoderLM``'s fields from the ``afmoe`` family's ``config.json``
    keys (``num_dense_layers``, ``num_experts``, ``num_shared_experts``,
    ``layer_types``, ``sliding_window``, ``num_key_value_heads``,
    ``head_dim``, ``route_scale``, ``load_balance_coeff``, ``mup_enabled``,
    ...) plus this repo's ``experts_held`` / ``first_expert`` /
    ``compute_dtype``. ``layer_types`` names each layer's attention; the
    first ``num_hidden_layers`` entries are used. Every block has sandwich
    norms; the first ``num_dense_layers`` a dense FFN, the rest the expert
    layer."""
    if cfg.get("score_func", "sigmoid") != "sigmoid" or \
            not cfg.get("route_norm", True) or cfg.get("n_group", 1) != 1:
        raise ValueError("DecoderLM routes by sigmoid scores, one group, "
                         "renormalised gates")
    if cfg.get("rope_scaling") or cfg.get("tie_word_embeddings"):
        raise ValueError("DecoderLM applies RoPE with no scaling and keeps "
                         "an untied head")
    layers = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg["layer_types"])[:layers]
    if len(kinds) != layers or \
            set(kinds) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types names {layers} layers as "
                         f"sliding_attention or full_attention, got {kinds}")
    hidden, e = int(cfg["hidden_size"]), int(cfg["num_experts"])
    heads = int(cfg["num_attention_heads"])
    eps = float(cfg.get("rms_norm_eps", 1e-5))
    attention = ("gqa", FrozenDict(
        num_heads=heads,
        num_kv_heads=int(cfg.get("num_key_value_heads", heads)),
        head_dim=int(cfg.get("head_dim", hidden // heads)),
        rope_theta=float(cfg["rope_theta"]), eps=eps))
    experts = ("experts", FrozenDict(
        n_routed_experts=e,
        experts_held=int(cfg.get("experts_held", e)),
        first_expert=int(cfg.get("first_expert", 0)),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        n_shared_experts=int(cfg.get("num_shared_experts", 0)),
        routed_scaling_factor=float(cfg.get("route_scale", 1.0)),
        bias_update_rate=float(cfg.get("load_balance_coeff", 1e-3))))
    dense, moe = (_two_sublayers(attention, ffn, _KEPT_ACROSS_REMAT,
                                 sandwich=True) for ffn
                  in (("swiglu", FrozenDict(width=int(
                      cfg["intermediate_size"]))), experts))
    k = int(cfg.get("num_dense_layers", 0))
    return dict(
        vocab_size=int(cfg["vocab_size"]), hidden_size=hidden,
        layers=tuple(dense if i < k else moe for i in range(layers)),
        layer_windows=tuple(int(cfg["sliding_window"])
                            if kind == "sliding_attention" else None
                            for kind in kinds),
        embed_scale=math.sqrt(hidden) if cfg.get("mup_enabled") else 1.0,
        rms_norm_eps=eps,
        dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")))


def _nemotron_h_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``DecoderLM``'s fields from the ``nemotron_h`` family's ``config.json``
    keys (``hybrid_override_pattern``, ``mamba_num_heads``,
    ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``, ``conv_kernel``,
    ``chunk_size``, ``moe_latent_size``, ``moe_shared_expert_intermediate_size``,
    ``mtp_hybrid_override_pattern``, ...) plus this repo's share keys. The
    pattern names each layer's one mixer; the first ``num_hidden_layers``
    letters are used. ``mixer_parallel_size`` t gives every Mamba-2 mixer
    ``1/t`` of its heads and of its B/C and norm groups, every attention
    layer ``1/t`` of its query heads and the kv heads they read (one where t
    exceeds them); ``mixer_parallel_rank`` says which: it picks a
    checkpoint's columns and rows, never a shape."""
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
    layers = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg["hybrid_override_pattern"])[:layers]
    mtp = int(cfg.get("num_nextn_predict_layers", 0))
    mtp_kinds = tuple(cfg.get("mtp_hybrid_override_pattern", "")) if mtp \
        else ()
    if len(kinds) != layers or set(kinds + mtp_kinds) - set("M*E"):
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
    mixers = {
        "M": ("mamba2", FrozenDict(
            num_heads=m_heads // t, head_dim=int(cfg["mamba_head_dim"]),
            n_groups=groups // t, state_size=int(cfg["ssm_state_size"]),
            conv_kernel=int(cfg["conv_kernel"]),
            chunk_size=int(cfg["chunk_size"]), eps=eps)),
        "*": ("gqa", FrozenDict(
            num_heads=heads // t, num_kv_heads=max(kv // t, 1),
            head_dim=int(cfg.get("head_dim", hidden // heads)),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            eps=eps, gated=False, qk_norm=False)),
        "E": ("experts", FrozenDict(
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
                                     0))))}
    # every block ONE mixer behind one norm
    block = {letter: Layer((Sublayer(*mixer, "norm"),),
                           _KEPT_WITH_FFN_PRODUCTS)
             for letter, mixer in mixers.items()}
    return dict(
        vocab_size=int(cfg["vocab_size"]), hidden_size=hidden,
        layers=tuple(block[k] for k in kinds),
        mtp_layers=tuple((f"mtp_layers_{i}", block[k])
                         for i, k in enumerate(mtp_kinds)),
        rms_norm_eps=eps,
        dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")),
        mtp_loss_weight=float(cfg.get("mtp_loss_weight", 0.3)))


def _granite_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``DecoderLM``'s fields from the ``granitemoehybrid`` family's
    ``config.json`` keys (``layer_types``, ``mamba_n_heads``,
    ``mamba_d_head``, ``mamba_n_groups``, ``mamba_d_state``,
    ``mamba_d_conv``, ``mamba_chunk_size``, ``shared_intermediate_size``,
    ``embedding_multiplier``, ``residual_multiplier``,
    ``attention_multiplier``, ``logits_scaling``, ...) plus
    ``compute_dtype``. ``layer_types`` names each layer's mixer; the first
    ``num_hidden_layers`` entries are used. Every block is its mixer, then a
    SwiGLU MLP (under ``mlp.dense``), each branch times
    ``residual_multiplier``."""
    if int(cfg.get("num_local_experts", 0)):
        raise ValueError("DecoderLM builds this family's dense form: no "
                         "expert layer (num_local_experts 0)")
    if cfg.get("position_embedding_type", "nope") != "nope":
        raise ValueError("DecoderLM builds this family's attention with no "
                         "position encoding (position_embedding_type nope)")
    if cfg.get("hidden_act", "silu") != "silu" or \
            cfg.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError("DecoderLM computes this family's MLP with SiLU and "
                         "its norms as RMSNorm")
    if cfg.get("attention_bias") or cfg.get("mamba_proj_bias") or \
            not cfg.get("mamba_conv_bias", True):
        raise ValueError("DecoderLM keeps no bias but the convolution's")
    layers = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg["layer_types"])[:layers]
    if len(kinds) != layers or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types names {layers} layers as mamba or "
                         f"attention, got {kinds}")
    hidden, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    m_heads, m_dim = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if m_heads * m_dim != int(cfg.get("mamba_expand", 2)) * hidden:
        raise ValueError(f"{m_heads} Mamba-2 heads of {m_dim} are not "
                         f"mamba_expand x hidden_size")
    eps = float(cfg.get("rms_norm_eps", 1e-5))
    mixers = {
        "mamba": ("mamba2", FrozenDict(
            num_heads=m_heads, head_dim=m_dim,
            n_groups=int(cfg["mamba_n_groups"]),
            state_size=int(cfg["mamba_d_state"]),
            conv_kernel=int(cfg["mamba_d_conv"]),
            chunk_size=int(cfg["mamba_chunk_size"]), eps=eps)),
        "attention": ("gqa", FrozenDict(
            num_heads=heads,
            num_kv_heads=int(cfg.get("num_key_value_heads", heads)),
            head_dim=hidden // heads,
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            eps=eps, gated=False, qk_norm=False,
            sm_scale=float(cfg["attention_multiplier"])))}
    mlp = ("swiglu", FrozenDict(width=int(cfg["shared_intermediate_size"])))
    block = {kind: _two_sublayers(
        mixer, mlp, _KEPT_WITH_FFN_PRODUCTS,
        residual_scale=float(cfg["residual_multiplier"]),
        ffn_scope="mlp.dense") for kind, mixer in mixers.items()}
    return dict(
        vocab_size=int(cfg["vocab_size"]), hidden_size=hidden,
        layers=tuple(block[k] for k in kinds),
        embed_scale=float(cfg["embedding_multiplier"]),
        logits_scale=float(cfg["logits_scaling"]),
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        rms_norm_eps=eps,
        dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")))


def _sdar_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``DecoderLM``'s fields from the ``sdar_moe`` family's ``config.json``
    keys (``num_experts``, ``num_key_value_heads``, ``head_dim``,
    ``norm_topk_prob``, ``decoder_sparse_step``, ``mlp_only_layers``, ...)
    plus this repo's ``experts_held`` / ``first_expert`` /
    ``compute_dtype`` and the block-diffusion training's ``block_length``
    (the config gives none: SDAR's default, 4), ``noise_eps`` and
    ``noise_seed``. Every block is attention, then the expert layer."""
    if int(cfg.get("decoder_sparse_step", 1)) != 1 or \
            cfg.get("mlp_only_layers"):
        raise ValueError("DecoderLM builds every sdar_moe block with the "
                         "expert layer (decoder_sparse_step 1, no "
                         "mlp_only_layers)")
    if not cfg.get("norm_topk_prob", True) or \
            cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("DecoderLM routes this family by softmax with the "
                         "chosen probabilities renormalised and SiLU experts")
    if cfg.get("rope_scaling") or cfg.get("use_sliding_window") or \
            cfg.get("tie_word_embeddings") or cfg.get("attention_bias"):
        raise ValueError("DecoderLM applies RoPE with no scaling and no "
                         "window, keeps an untied head and no bias")
    hidden, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    e = int(cfg["num_experts"])
    block = int(cfg.get("block_length", 4))
    if block < 1 or block & (block - 1):
        raise ValueError(f"block_length {block} is no power of two")
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    layer = _two_sublayers(
        ("gqa", FrozenDict(
            num_heads=heads,
            num_kv_heads=int(cfg.get("num_key_value_heads", heads)),
            head_dim=int(cfg.get("head_dim", hidden // heads)),
            rope_theta=float(cfg["rope_theta"]), eps=eps, gated=False,
            qk_norm=True, block_diffusion=block)),
        ("experts", FrozenDict(
            n_routed_experts=e,
            experts_held=int(cfg.get("experts_held", e)),
            first_expert=int(cfg.get("first_expert", 0)),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            n_shared_experts=0, routed_scaling_factor=1.0,
            bias_update_rate=0.0, scoring="softmax")),
        _KEPT_ACROSS_REMAT)
    return dict(
        vocab_size=int(cfg["vocab_size"]), hidden_size=hidden,
        layers=(layer,) * int(cfg["num_hidden_layers"]),
        rms_norm_eps=eps,
        dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")),
        diffusion_block=block,
        noise_eps=float(cfg.get("noise_eps", 1e-3)),
        noise_seed=int(cfg.get("noise_seed", 0)))


def _keye_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``DecoderLM``'s fields from the ``KeyeVL2`` family's language model
    keys (a Qwen3-MoE-style decoder: ``num_experts``,
    ``num_key_value_heads``, ``head_dim``, ``norm_topk_prob``,
    ``rope_scaling.mrope_section``, and DeepSeek Sparse Attention's
    ``sa_config``: ``indexer_num_heads``, ``indexer_head_dim``,
    ``indexer_num_kv_heads``, ``topk``, ``q_chunk_size``,
    ``kv_chunk_size``) plus this repo's ``experts_held`` / ``first_expert``
    / ``compute_dtype`` / ``indexer_loss_weight`` (lambda, 1 by default).
    Every block is learned sparse attention (q/k norms, MRoPE, the indexer,
    the selected keys), then the expert layer routed by softmax. The vision
    tower is not built: the ids are text."""
    if int(cfg.get("decoder_sparse_step", 1)) != 1 or \
            cfg.get("mlp_only_layers"):
        raise ValueError("DecoderLM builds every KeyeVL2 block with the "
                         "expert layer (decoder_sparse_step 1, no "
                         "mlp_only_layers)")
    if not cfg.get("norm_topk_prob", True) or \
            cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("DecoderLM routes this family by softmax with the "
                         "chosen probabilities renormalised and SiLU experts")
    if cfg.get("use_sliding_window") or cfg.get("tie_word_embeddings") or \
            cfg.get("attention_bias"):
        raise ValueError("DecoderLM applies no window, keeps an untied head "
                         "and no bias")
    scaling = dict(cfg.get("rope_scaling") or {})
    section = tuple(int(n) for n in scaling.pop("mrope_section", ()))
    if {scaling.get("rope_type", "default"),
            scaling.get("type", "default")} != {"default"}:
        raise ValueError(f"DecoderLM applies MRoPE with no scaling, got "
                         f"{cfg.get('rope_scaling')}")
    sa = dict(cfg["sa_config"])
    if int(sa.get("indexer_num_kv_heads", 1)) != 1 or \
            int(sa.get("kv_chunk_size", sa["q_chunk_size"])) != \
            int(sa["q_chunk_size"]):
        raise ValueError("DecoderLM's indexer has one key head and works in "
                         "equal query and key chunks")
    hidden, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    d = int(cfg.get("head_dim", hidden // heads))
    if section and sum(section) != d // 2:
        raise ValueError(f"mrope_section {section} does not cover the "
                         f"{d // 2} frequency pairs of a head")
    e = int(cfg["num_experts"])
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    layer = _two_sublayers(
        ("gqa", FrozenDict(
            num_heads=heads,
            num_kv_heads=int(cfg.get("num_key_value_heads", heads)),
            head_dim=d, rope_theta=float(cfg["rope_theta"]), eps=eps,
            gated=False, qk_norm=True,
            indexer_heads=int(sa["indexer_num_heads"]),
            indexer_head_dim=int(sa["indexer_head_dim"]),
            indexer_topk=int(sa["topk"]),
            indexer_rows=int(sa["q_chunk_size"]),
            mrope_section=section or None)),
        ("experts", FrozenDict(
            n_routed_experts=e,
            experts_held=int(cfg.get("experts_held", e)),
            first_expert=int(cfg.get("first_expert", 0)),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            n_shared_experts=0, routed_scaling_factor=1.0,
            bias_update_rate=0.0, scoring="softmax")),
        _KEPT_WITH_SELECTION)
    return dict(
        vocab_size=int(cfg["vocab_size"]), hidden_size=hidden,
        layers=(layer,) * int(cfg["num_hidden_layers"]),
        rms_norm_eps=eps,
        dtype=jnp.dtype(cfg.get("compute_dtype", "bfloat16")),
        indexer_loss_weight=float(cfg.get("indexer_loss_weight", 1.0)))


# the six families by the model_type names each is published under, and
# the readers of their keys
MODEL_TYPES = {
    "deepseek_v3": ("deepseek_v3", "joyai_llm_flash"),
    "afmoe": ("afmoe",),
    "nemotron_h": ("nemotron_h",),
    "granitemoehybrid": ("granitemoehybrid",),
    "sdar_moe": ("sdar_moe",),
    "keye_vl2": ("KeyeVL2",),
}
_FIELDS = {"deepseek_v3": _deepseek_fields, "afmoe": _afmoe_fields,
           "nemotron_h": _nemotron_h_fields,
           "granitemoehybrid": _granite_fields, "sdar_moe": _sdar_fields,
           "keye_vl2": _keye_fields}


def block_diffusion_noise(key, batch: int, length: int, block: int,
                          eps: float = 1e-3):
    """The block-diffusion noise of a batch: ``(masked (batch, length) bool,
    p (batch, length) float32)``. Each block of ``block`` positions draws
    ``t ~ U(0, 1)`` and masks each of its positions with ``p = (1 - eps) t +
    eps`` (LLaDA's schedule), independently: ``jax.random.split(key)``'s
    first key draws the (batch, length / block) ``t``, its second the
    (batch, length) uniforms compared with ``p``."""
    k_t, k_m = jax.random.split(key)
    t = jax.random.uniform(k_t, (batch, length // block), jnp.float32)
    p = jnp.repeat((1.0 - eps) * t + eps, block, axis=1)
    return jax.random.uniform(k_m, (batch, length), jnp.float32) < p, p


def block_diffusion_loss(ids, preds):
    """``(1/L) sum_i w_i CE(logits_i, ids_i)`` for each sequence of L ids
    (the engine takes the mean over the batch), ``preds`` the model's
    ``(logits (batch, L, vocab), w = m / p (batch, L))``: a masked
    position's logits score its own token, no shift; the MASK token is
    never a label."""
    logits, weight = preds
    with jax.named_scope("bd.loss"):
        ids = ids.astype(jnp.int32)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * weight, axis=-1) / ids.shape[1]


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


def sparse_attention_loss(ids, preds, indexer_weight: float = 1.0):
    """``CE(logits, tok_{t+1}) + indexer_weight * L_I`` for each sequence
    (the engine takes the mean over the batch), ``preds`` the model's
    ``(logits, L_I)``: ``L_I`` carries gradient to the indexers alone, the
    cross-entropy to everything else (the indexers read their input
    detached, their target is not differentiated)."""
    logits, indexer_loss = preds
    return _shifted_nll(logits, ids.astype(jnp.int32), 1) + \
        indexer_weight * indexer_loss


def sa_counters(extra_vars: Dict[str, Any]) -> Dict[str, Any]:
    """The selected-key layers' last step from a step's outputs (the
    engine's ``extra_vars``), one host fetch: ``sa_indexer_kl`` (the mean
    ``L_I`` over the layers), ``sa_layer_kl`` (each layer's),
    ``sa_entries_computed`` / ``sa_entries_needed`` (summed over the
    layers, a head and a forward pass), ``sa_key_bins`` (each layer's
    ``key_bins``), ``sa_steps``. Each call adds the step's entries to
    ``zoo_attention_sa_entries_total`` and sets the ``zoo_sa_indexer_kl``
    gauge."""
    stats = jax.device_get(extra_vars.get(SA_STATS, {}))
    layers = {k: v["self_attn"] for k, v in stats.items()
              if "self_attn" in v}
    if not layers:
        return {}
    out = {
        "sa_layer_kl": {k: float(v["kl"]) for k, v in layers.items()},
        "sa_entries_computed": float(sum(float(v["entries_computed"])
                                         for v in layers.values())),
        "sa_entries_needed": float(sum(float(v["entries_needed"])
                                       for v in layers.values())),
        "sa_key_bins": {k: np.asarray(v["key_bins"])
                        for k, v in layers.items()},
        "sa_steps": max(int(v["steps"]) for v in layers.values()),
    }
    out["sa_indexer_kl"] = sum(out["sa_layer_kl"].values()) / len(layers)
    SA_ENTRIES.labels(kind="computed").inc(out["sa_entries_computed"])
    SA_ENTRIES.labels(kind="needed").inc(out["sa_entries_needed"])
    _REGISTRY.gauge("zoo_sa_indexer_kl", "the indexer's KL loss L_I, the "
                    "mean over the selected-key layers, last step read"
                    ).set(out["sa_indexer_kl"])
    return out


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
