"""Hybrid decoder-only LM — gated short-convolution layers beside a few
grouped-query attention layers, a dense SwiGLU feed-forward in the leading
layers and a sigmoid-routed SwiGLU feed-forward after them — served through
the paged programs of ``models/causal_lm.py``.

(ref: none — the block of LFM2's mixture-of-experts models, ``lfm2_moe``.)
``layer_types`` names each layer's mixer (``"conv"`` or
``"full_attention"``); the first ``num_dense_layers`` layers have the dense
feed-forward, the others the routed one. Every layer is::

    h <- h + mixer(RMSNorm(h))
    h <- h + ffn(RMSNorm(h))

RMSNorm is ``x rsqrt(mean x^2 + eps) gamma`` in float32, ``eps =
rms_norm_eps``. After the last layer the final RMSNorm and a head TIED to
the embedding (``logits = h E^T``).

- ``conv``, the gated short convolution. ``[B | C | x~] = h W_in`` (``d ->
  3 d``, no bias, the thirds in that order); ``u = B * x~``; a depthwise
  causal convolution of ``conv_kernel`` taps without bias, ``v_t = sum_k
  w_k * u_{t - (taps - 1) + k}``; ``y = (C * v) W_out``. No activation.
  **A sequence's state is the last ``conv_kernel - 1`` rows of ``u``**
  (compute dtype; a fresh slot starts from zeros): a pool a layer
  addressed by SLOT (``kv_cache_ops.StatePool``) in
  ``pallas/short_conv_update.window_shape``'s whole tiles. Prefill runs
  ``CausalConv1D`` over ``u`` (the window carried across a call's rows),
  decode ``ShortConvUpdate`` (the window updated in place).
- ``full_attention``. ``num_heads`` query and ``num_kv_heads`` key-value
  heads of ``head_dim``, no bias; an RMSNorm over ``head_dim`` on q and on
  k (``rms_norm_eps``), then RoPE (rotate-half pairs over the whole head,
  ``rope_theta``); ``softmax(q k^T / sqrt(head_dim))`` causal; K and V in
  paged caches under the page table, read in place
  (``PagedDecodeAttention``, grouped queries).
- dense feed-forward: ``W_2(silu(W_1 b) * W_3 b)`` of ``dense_width``.
- routed feed-forward: ``s = sigmoid(b W_r)`` over ALL ``num_experts`` in
  float32 (the product at the highest precision); the ``experts_per_token``
  of largest ``s + bias`` (the bias chooses, it does not weigh; a tie to
  the lower expert); gates ``s_e / (sum_selected s + gate_norm_eps)`` x
  ``routed_scaling_factor``; every expert a SwiGLU of ``expert_width``; no
  shared expert. Every expert is held here (``RoutedFFN`` without
  ``held``): the dispatch is two grouped matmuls over ``T x
  experts_per_token`` pairs.

THE PROGRAM CONTRACT is ``models/state_space_moe_lm.py``'s: state pools,
so ``slots`` and ``lens`` feeds, ``state_outside_pages``, no prefix hits
and no draft. Serving only: there is no training graph for this block
(ROADMAP X0).
"""

from __future__ import annotations

import dataclasses

import numpy as np

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.models.causal_lm import (
    CausalLMGenerativeModel, PreNormStack, build_paged_lm_program)
from simple_tensorflow_tpu.models.latent_moe_lm import (
    sample_held_expert_counts)
from simple_tensorflow_tpu.models.state_space_moe_lm import (
    _state_bytes_share, _state_pool_bytes)
from simple_tensorflow_tpu.ops.pallas.short_conv_update import window_shape

_CONV, _ATTENTION = "conv", "full_attention"
# the published layer_types: after the two leading dense layers the
# period is (attention, conv, conv, conv)
LFM2_LAYER_TYPES = ((_CONV, _CONV) + (_ATTENTION, _CONV, _CONV, _CONV) * 4
                    + (_ATTENTION, _CONV, _CONV, _ATTENTION, _CONV, _CONV))


@dataclasses.dataclass
class ShortConvMoEConfig:
    vocab_size: int = 65536
    d_model: int = 2048
    layer_types: tuple = LFM2_LAYER_TYPES
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    conv_kernel: int = 3
    dense_width: int = 7168
    num_experts: int = 32
    experts_per_token: int = 4
    expert_width: int = 1792
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    gate_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    max_len: int = 128000
    pad_id: int = 0
    eos_id: int = 1

    @staticmethod
    def tiny():
        return ShortConvMoEConfig(
            vocab_size=96, d_model=128,
            layer_types=(_CONV, _ATTENTION, _CONV, _CONV, _ATTENTION),
            num_dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=64,
            dense_width=96, num_experts=8, experts_per_token=4,
            expert_width=32, max_len=64)

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if set(self.layer_types) - {_CONV, _ATTENTION}:
            raise ValueError(f"layer_types {self.layer_types!r}: each "
                             f"{_CONV!r} or {_ATTENTION!r}")

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def layer_kinds(self):
        return self.layer_types

    @property
    def state_shape(self):
        """Inner shape of a conv layer's window pool, as stored."""
        return window_shape(self.conv_kernel, self.d_model)

    def state_bytes_per_slot(self, compute_dtype_size):
        """One sequence's windows over all conv layers."""
        return (self.layer_types.count(_CONV) * (self.conv_kernel - 1)
                * self.d_model * compute_dtype_size)

    def kv_bytes_per_token(self, compute_dtype_size):
        return (self.layer_types.count(_ATTENTION) * 2 * self.num_kv_heads
                * self.head_dim * compute_dtype_size)


class _ShortConvMoEStack(PreNormStack):
    """The block stack as ``build_paged_lm_program`` sees one: a mixer by
    ``cfg.layer_types`` and a feed-forward a layer."""

    keeps_state = True

    def layer_caches(self, kvc, total_pages, page_len, sharding,
                     state_slots):
        cfg = self.cfg

        def of(i, kind):
            name = f"{self.scope}_pg/l{i}_"
            if kind == _CONV:
                return (kvc.state_pool(name + "conv", state_slots,
                                       cfg.state_shape, self.compute_dtype),)
            return tuple(
                kvc.kv_cache(name + which, total_pages, page_len,
                             (cfg.num_kv_heads, cfg.head_dim),
                             self.compute_dtype, sharding=sharding,
                             paged=True) for which in "kv")

        return [of(i, kind) for i, kind in enumerate(cfg.layer_types)]

    # -- the two mixers -------------------------------------------------------
    def _conv(self, i, u, rows, lead, positions, cache, block):
        cfg = self.cfg
        d, taps = cfg.d_model, cfg.conv_kernel
        bcx = stf.matmul(u, self._w("conv/in", [d, 3 * d], d))
        w = self._w("conv/w", [taps, d], taps)
        pool, = cache.state(i)
        if block:
            b, c, x = stf.split(bcx, 3, axis=-1)
            v = stf.nn.causal_conv1d(
                stf.reshape(b * x, lead + [d]), w,
                stf.zeros([d], stf.float32), pool, cache.slots,
                cache.fresh(), cache.lens)
            y = c * stf.reshape(v, [rows, d])
        else:
            y = stf.nn.short_conv_update(bcx, w, pool, cache.slots,
                                         cache.fresh())
        return stf.matmul(y, self._w("conv/out", [d, d], d))

    def _attention(self, i, u, rows, lead, positions, cache, block):
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.head_dim
        width = cfg.num_heads * hd

        def heads(name, count):
            return stf.reshape(
                stf.matmul(u, self._w(name, [d, count * hd], d)),
                lead + [count, hd])

        def normed(name, count):
            return stf.nn.rotary_embedding(
                self._norm(heads(name, count), name + "_norm", hd),
                positions, cfg.rope_theta)

        q = normed("attn/q", cfg.num_heads)
        k = normed("attn/k", cfg.num_kv_heads)
        v = heads("attn/v", cfg.num_kv_heads)
        if block:
            o = cache.attend_block(i, q, k, v)
        else:                       # (B, 1, H, D): one position a row
            o = cache.attend(i, stf.reshape(q, [rows, cfg.num_heads, hd]),
                             k, v)
        return stf.matmul(stf.reshape(o, [rows, width]),
                          self._w("attn/out", [width, d], width))

    # -- the two feed-forwards -----------------------------------------------
    def _ffn(self, i, x, row_mask):
        cfg = self.cfg
        d = cfg.d_model
        if i < cfg.num_dense_layers:
            width = cfg.dense_width
            gate, up = stf.split(stf.matmul(
                self._norm(x, "ln2", d),
                self._w("ffn/gate_up", [d, 2 * width], d)), 2, axis=-1)
            return stf.matmul(stf.nn.silu(gate) * up,
                              self._w("ffn/down", [width, d], width)), None
        e, width = cfg.num_experts, cfg.expert_width
        y, counts = stf.nn.routed_ffn(
            self._norm(x, "ln2", d, out_dtype="float32"),
            self._w("moe/router", [d, e], d, dtype=stf.float32),
            self._w("moe/gate_up", [e, d, 2 * width], d),
            self._w("moe/down", [e, width, d], width),
            row_mask, top_k=cfg.experts_per_token,
            norm_topk=cfg.norm_topk_prob, score="sigmoid",
            bias=stf.get_variable(
                "moe/bias", [e], dtype=stf.float32,
                initializer=stf.random_normal_initializer(stddev=0.01)),
            gate_scale=cfg.routed_scaling_factor,
            gate_norm_eps=cfg.gate_norm_eps)
        return stf.cast(y, self.compute_dtype), counts

    def _layer(self, kind, i, x, rows, lead, positions, attend, row_mask):
        cache, block = attend
        mixer = self._conv if kind == _CONV else self._attention
        x = x + mixer(i, self._norm(x, "ln1", self.cfg.d_model), rows, lead,
                      positions, cache, block)
        y, counts = self._ffn(i, x, row_mask)
        return x + y, counts

    # -- the two programs -----------------------------------------------------
    def prefill_block(self, tok, base, cache):
        b, s = int(tok.shape[0]), int(tok.shape[1])
        positions = stf.reshape(base, [b, 1]) + stf.constant(
            np.arange(s, dtype=np.int32).reshape(1, s))
        with stf.variable_scope(self.scope, reuse=stf.AUTO_REUSE):
            x = stf.reshape(self._embed(tok), [b * s, self.cfg.d_model])
            x, _ = self._layers(x, b * s, [b, s], positions, (cache, True))
        return x

    def decode_step(self, tok, pos, cache):
        b = int(tok.shape[0])
        with stf.variable_scope(self.scope, reuse=stf.AUTO_REUSE):
            x, counts = self._layers(self._embed(tok), b, [b, 1],
                                     stf.reshape(pos, [b, 1]),
                                     (cache, False),
                                     row_mask=cache.live_rows())
            logits = self._logits(x, tied=True)
        # (a cut without a routed layer has no histogram to return)
        return logits, ({"expert_counts": stf.stack(counts)} if counts
                        else {})


class ShortConvMoEGenerativeModel(CausalLMGenerativeModel):
    """Session-owning paged serving programs of the short-convolution
    routed-FFN decoder; the engine-facing half is
    :class:`CausalLMGenerativeModel`'s — with ``slots`` and ``lens``, since
    ``state_outside_pages`` — the block stack is this module's.

    ``metrics_label`` (the base class's) labels the gauge
    ``/stf/serving/state_pool_bytes`` and the per-step samplers:
    ``state_bytes_share``, ``moe_load_imbalance`` over all the experts,
    ``moe_local_pair_share`` (every pair is local here) and
    ``decode_live_page_share``.
    """

    def __init__(self, cfg: ShortConvMoEConfig, *, pages_per_seq=4, **kw):
        for unsupported in ("int8", "mesh", "tp"):
            if kw.get(unsupported):
                raise ValueError(f"{type(self).__name__} has no "
                                 f"{unsupported}= path")
        super().__init__(cfg, pages_per_seq=pages_per_seq, **kw)
        _state_pool_bytes.get_cell(self._metrics_label).set(
            self._state_pool_bytes())

    def _state_pool_bytes(self):
        return (self.num_slots + 1) * self.cfg.state_bytes_per_slot(
            self._compute_dtype.size)

    def _cache_bytes(self):
        """Both kinds of pool: K/V pages and windows by slot."""
        pages = (self.cfg.kv_bytes_per_token(self._compute_dtype.size)
                 * self.num_pages * self.page_len)
        total = pages + self._state_pool_bytes()
        return total, total

    def _build_program(self, *, compute_dtype, scope, tp_axis, **kw):
        return build_paged_lm_program(
            _ShortConvMoEStack(self.cfg, compute_dtype, scope),
            compute_dtype=compute_dtype, scope=scope, **kw)

    def _after_decode(self, out, n, positions):
        super()._after_decode(out, n, positions)       # live page share
        cfg, size = self.cfg, self._compute_dtype.size
        live_pages = -(-(np.asarray(positions[:n], np.int64) + 1)
                       // self.page_len)
        state = n * cfg.state_bytes_per_slot(size)
        pages = (live_pages.sum() * self.page_len
                 * cfg.kv_bytes_per_token(size))
        _state_bytes_share.get_cell(self._metrics_label).add(
            float(state / (state + pages)))
        if "expert_counts" in out:
            sample_held_expert_counts(self._metrics_label,
                                      out["expert_counts"], n,
                                      cfg.experts_per_token)
