"""Decoder-only LM with multi-head LATENT attention and a sigmoid-routed
feed-forward with a shared expert, served through the paged programs of
``models/causal_lm.py`` as ONE CHIP'S SHARE of a deployment that divides
every routed layer's experts over several chips.

(ref: none — the block of recent open latent-attention mixture-of-experts
decoders.) For a token's hidden state ``x`` at position ``t``, every layer:

- ``a = RMSNorm(x)``. Query: ``c_q = RMSNorm(a.W_qa)`` (``q_lora_rank``);
  ``q = c_q.W_qb`` -> ``num_heads`` x (``qk_nope_head_dim`` "nope" +
  ``qk_rope_head_dim`` "rope"); RoPE on the rope part. Latent: ``[c_kv ;
  k_r] = a.W_kva`` (``kv_lora_rank`` + ``qk_rope_head_dim``); ``c_kv <-
  RMSNorm(c_kv)``; ``k_r <- RoPE(k_r)``, ONE rope key for all heads. The
  two latent norms take ``latent_norm_eps``. **The cache row is ``[c_kv ;
  k_r]``**, zero-padded to whole 128-lane tiles (``latent_row``).
- Plain form (the published description, and the benchmark reference's):
  ``[k_nope_h ; v_h] = c_kv.W_kvb`` -> heads x (nope + ``v_head_dim``);
  ``score_h[t, s] = sigma (q_nope_h . k_nope_h[s] + q_rope_h . k_r[s])``
  for ``s <= t``; float32 softmax; ``o_h = sum p v_h[s]``; ``x +=
  concat_h(o_h).W_o``.
- ABSORBED form (what both programs here run): ``q~_h = q_nope_h.W_kvb^K_h``
  (``kv_lora_rank``); ``score_h = sigma (q~_h . c_kv[s] + q_rope_h .
  k_r[s])`` — one product of ``[q~_h ; q_rope_h]`` with the cache row;
  ``u_h = sum p c_kv[s]``; ``o_h = u_h.W_kvb^V_h``. The same numbers as the
  plain form up to rounding; nothing per head is ever cached or built.
- ``sigma = (nope + rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor) +
  1``. RoPE is rotate-half over the rope dimensions with YaRN's blended
  frequencies (``ops/sparse_attention_ops.yarn_inv_freq``), static: the
  same at every length; cos and sin scaled by ``yarn_mscale(factor, mscale)
  / yarn_mscale(factor, mscale_all_dim)``.
- ``b = RMSNorm(x)``. Layers ``< dense_layers``: ``x += W_down(silu(W_gate
  b) * W_up b)``, width ``dense_width``. The others: ``s = sigmoid(b.W_r)``
  over ALL ``num_experts`` in float32; the ``experts_per_token`` experts of
  largest ``s + bias`` (a tie to the lower expert); gates ``g_e = s_e /
  (sum_selected s + 1e-20) * routed_scaling_factor`` — the bias chooses, it
  does not weigh; ``x += sum_e g_e E_e(b) + S(b)``, every ``E_e`` and the
  shared expert ``S`` a SwiGLU of width ``expert_width``. No token dropped.
- after the last layer RMSNorm and an UNTIED head.

THE SHARE. ``held_experts = (first, count)``: this chip holds ``count`` of
the ``num_experts`` experts of every routed layer (their weights are
``(count, ...)``). The router keeps its width and its experts per token;
``RoutedFFN`` dispatches only the pairs whose expert is held and returns
this chip's part of the sum (``ops/moe_ops.py``), the shared expert is
added here, once, and what the absent experts would add is left out — that
partial result goes on to the next layer (model-configs guide, section 4).
``vocab_size`` is the slice of the vocabulary held here: a smaller
vocabulary. Attention is data-parallel in such a deployment: all heads are
here. No code stands in for the absent chips or their exchange.

One paged cache a layer under the page table, of latent rows. Both
programs attend through ``PagedLatentAttention``, whose Pallas kernel
reads the pool in place (``ops/pallas/latent_attention.py``): DECODE one
``(num_heads, latent_row)`` query tile a sequence, PREFILL a page-aligned
block of queries a head at a time against the committed prefix plus
itself. Variables, norm, embedding, the pre-norm layer loop and the head
are ``causal_lm.PreNormStack``'s, shared with ``models/sparse_moe_lm.py``.
Serving only: there is no training graph for this block (ROADMAP X0).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.models.causal_lm import (
    CausalLMGenerativeModel, PreNormStack, build_paged_lm_program)
from simple_tensorflow_tpu.models.sparse_moe_lm import _moe_imbalance
from simple_tensorflow_tpu.platform import monitoring

_local_pair_share = monitoring.Sampler(
    "/stf/serving/moe_local_pair_share",
    monitoring.ExponentialBuckets(0.001, 1.5, 18),
    "Per decode step: (live row, expert) pairs that landed on the experts "
    "this chip holds over all the live rows' pairs, over the routed "
    "layers: held / num_experts when routing is even", "model")


def sample_held_expert_counts(label, counts, rows, experts_per_token):
    """Once a decode step, from the (routed layers x held experts)
    histogram of live rows: ``moe_load_imbalance`` over the held experts
    and ``moe_local_pair_share``."""
    counts = np.asarray(counts, np.float64)
    mean = counts.mean(axis=-1)
    if mean.all():
        _moe_imbalance.get_cell(label).add(
            float((counts.max(axis=-1) / mean).mean()))
    _local_pair_share.get_cell(label).add(
        float(counts.sum() / (len(counts) * rows * experts_per_token)))


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass
class LatentMoEConfig:
    vocab_size: int = 163840
    d_model: int = 7168
    num_layers: int = 61
    num_heads: int = 64
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    rms_norm_eps: float = 1e-5
    latent_norm_eps: float = 1e-6
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    dense_layers: int = 1
    dense_width: int = 18432
    num_experts: int = 384
    experts_per_token: int = 8
    expert_width: int = 2048
    routed_scaling_factor: float = 2.827
    norm_topk_prob: bool = True
    # (first, count) of the experts this chip holds; None = all of them
    held_experts: tuple | None = None
    max_len: int = 262144
    pad_id: int = 0
    eos_id: int = 1

    @staticmethod
    def tiny():
        return LatentMoEConfig(
            vocab_size=96, d_model=64, num_layers=3, num_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            q_lora_rank=32, kv_lora_rank=24, rope_theta=100.0,
            rope_factor=4.0, rope_original_len=16, dense_layers=1,
            dense_width=96, num_experts=16, experts_per_token=4,
            expert_width=32, held_experts=(4, 8), max_len=64)

    @property
    def held(self):
        return tuple(self.held_experts or (0, self.num_experts))

    @property
    def latent_row(self):
        """Width of the stored cache row: ``[c_kv ; k_r]`` padded to whole
        128-lane tiles (``ops/pallas/latent_attention.py`` says why)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def yarn(self):
        return (self.rope_factor, self.rope_original_len,
                self.rope_beta_fast, self.rope_beta_slow)

    @property
    def rope_amplitude(self):
        return (yarn_mscale(self.rope_factor, self.rope_mscale)
                / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))

    @property
    def softmax_scale(self):
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


class _LatentMoEStack(PreNormStack):
    """The block stack as ``build_paged_lm_program`` sees one."""

    def layer_caches(self, kvc, total_pages, page_len, sharding):
        return [(kvc.kv_cache(f"{self.scope}_pg/l{i}_latent", total_pages,
                              page_len, (self.cfg.latent_row,),
                              self.compute_dtype, sharding=sharding,
                              paged=True),)
                for i in range(self.cfg.num_layers)]

    # -- pieces -----------------------------------------------------------
    def _rope(self, x, positions):
        cfg = self.cfg
        return stf.nn.rotary_embedding(x, positions, cfg.rope_theta,
                                       yarn=cfg.yarn,
                                       amplitude=cfg.rope_amplitude)

    def _attention(self, i, a, rows, lead, positions, attend):
        """Absorbed latent attention of ``a (rows, d_model)``; ``attend(i,
        q (lead, H, latent_row), row (lead, latent_row))`` appends the
        rows and returns ``u (lead, H, kv_lora_rank)``."""
        cfg = self.cfg
        d, h = cfg.d_model, cfg.num_heads
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        v_dim, qr, kr = cfg.v_head_dim, cfg.q_lora_rank, cfg.kv_lora_rank
        pad = cfg.latent_row - kr - rope
        eps = cfg.latent_norm_eps

        c_q = self._norm(stf.matmul(a, self._w("attn/q_a", [d, qr], d)),
                         "attn/q_norm", qr, eps=eps)
        q = stf.reshape(
            stf.matmul(c_q, self._w("attn/q_b", [qr, h * (nope + rope)], qr)),
            lead + [h, nope + rope])
        q_nope, q_rope = stf.split(q, [nope, rope], axis=-1)
        kv = stf.matmul(a, self._w("attn/kv_a", [d, kr + rope], d))
        c_kv, k_r = stf.split(kv, [kr, rope], axis=-1)
        c_kv = self._norm(c_kv, "attn/kv_norm", kr, eps=eps)
        k_r = stf.reshape(
            self._rope(stf.reshape(k_r, lead + [1, rope]), positions),
            [rows, rope])
        w_k, w_v = stf.split(
            stf.reshape(self._w("attn/kv_b", [kr, h * (nope + v_dim)], kr),
                        [kr, h, nope + v_dim]), [nope, v_dim], axis=-1)
        parts = [stf.einsum("bshn,chn->bshc", q_nope, w_k),
                 self._rope(q_rope, positions)]
        row = [c_kv, k_r]
        if pad:
            parts.append(stf.zeros(lead + [h, pad], self.compute_dtype))
            row.append(stf.zeros([rows, pad], self.compute_dtype))
        u = attend(i, stf.concat(parts, axis=-1),
                   stf.reshape(stf.concat(row, axis=-1),
                               lead + [cfg.latent_row]))
        o = stf.einsum("bshc,chv->bshv", stf.reshape(u, lead + [h, kr]), w_v)
        return stf.matmul(stf.reshape(o, [rows, h * v_dim]),
                          self._w("attn/out", [h * v_dim, d], h * v_dim))

    def _swiglu(self, b, name, width):
        d = self.cfg.d_model
        gate, up = stf.split(
            stf.matmul(b, self._w(f"{name}gate_up", [d, 2 * width], d)),
            2, axis=-1)
        return stf.matmul(stf.nn.silu(gate) * up,
                          self._w(f"{name}down", [width, d], width))

    def _ffn(self, i, x, row_mask):
        cfg = self.cfg
        d = cfg.d_model
        if i < cfg.dense_layers:
            return self._swiglu(self._norm(x, "ln2", d), "ffn/",
                                cfg.dense_width), None
        e, width = cfg.num_experts, cfg.expert_width
        held = cfg.held
        b = self._norm(x, "ln2", d, out_dtype="float32")
        y, counts = stf.nn.routed_ffn(
            b, self._w("moe/router", [d, e], d, dtype=stf.float32),
            self._w("moe/gate_up", [held[1], d, 2 * width], d),
            self._w("moe/down", [held[1], width, d], width),
            row_mask, top_k=cfg.experts_per_token,
            norm_topk=cfg.norm_topk_prob, score="sigmoid",
            bias=stf.get_variable(
                "moe/bias", [e], dtype=stf.float32,
                initializer=stf.random_normal_initializer(stddev=0.01)),
            gate_scale=cfg.routed_scaling_factor, held=held)
        # the shared expert: once, whatever is held here
        shared = self._swiglu(stf.cast(b, self.compute_dtype),
                              "moe/shared_", width)
        return stf.cast(y + stf.cast(shared, stf.float32),
                        self.compute_dtype), counts

    # -- the two programs -----------------------------------------------------
    def _attend_through(self, cache, block):
        cfg = self.cfg

        def attend(i, q, row):
            if not block:               # (B, 1, H, W): one position a row
                q = stf.reshape(q, [int(q.shape[0]), cfg.num_heads,
                                    cfg.latent_row])
            return cache.attend_latent(
                i, q, row, value_dim=cfg.kv_lora_rank,
                sm_scale=cfg.softmax_scale, block=block)
        return attend

    def prefill_block(self, tok, base, cache):
        cfg = self.cfg
        b, s = int(tok.shape[0]), int(tok.shape[1])
        positions = stf.reshape(base, [b, 1]) + stf.constant(
            np.arange(s, dtype=np.int32).reshape(1, s))
        with stf.variable_scope(self.scope, reuse=stf.AUTO_REUSE):
            x = stf.reshape(self._embed(tok), [b * s, cfg.d_model])
            x, _ = self._layers(x, b * s, [b, s], positions,
                                self._attend_through(cache, True))
        return x

    def decode_step(self, tok, pos, cache):
        b = int(tok.shape[0])
        with stf.variable_scope(self.scope, reuse=stf.AUTO_REUSE):
            x, counts = self._layers(
                self._embed(tok), b, [b, 1], stf.reshape(pos, [b, 1]),
                self._attend_through(cache, False),
                row_mask=cache.live_rows())
            logits = self._logits(x)
        return logits, {"expert_counts": stf.stack(counts)}


class LatentMoEGenerativeModel(CausalLMGenerativeModel):
    """Session-owning paged serving programs of the latent-attention
    routed-FFN decoder; the engine-facing half (``prefill_chunk``,
    ``decode``, ``copy_page``, buckets, page geometry) is
    :class:`CausalLMGenerativeModel`'s, the block stack is this module's.

    ``metrics_label`` (the base class's) labels the per-step samplers:
    ``/stf/serving/moe_load_imbalance`` over the HELD experts,
    ``moe_local_pair_share``, and — its attention reads whole live pages,
    as the dense model's does — ``decode_live_page_share``.
    """

    def __init__(self, cfg: LatentMoEConfig, *, pages_per_seq=4, **kw):
        for unsupported in ("int8", "mesh", "tp"):
            if kw.get(unsupported):
                raise ValueError(f"{type(self).__name__} has no "
                                 f"{unsupported}= path")
        super().__init__(cfg, pages_per_seq=pages_per_seq, **kw)

    def _cache_bytes(self):
        cfg = self.cfg
        total = (cfg.latent_row * cfg.num_layers * self.num_pages
                 * self.page_len * self._compute_dtype.size)
        return total, total

    def _build_program(self, *, compute_dtype, scope, tp_axis, **kw):
        return build_paged_lm_program(
            _LatentMoEStack(self.cfg, compute_dtype, scope),
            compute_dtype=compute_dtype, scope=scope, **kw)

    def _after_decode(self, out, n, positions):
        super()._after_decode(out, n, positions)       # live page share
        sample_held_expert_counts(self._metrics_label, out["expert_counts"],
                                  n, self.cfg.experts_per_token)
