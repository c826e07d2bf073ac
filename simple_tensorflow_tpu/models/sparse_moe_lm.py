"""Decoder-only LM with learned sparse attention and routed feed-forward
layers, served through the paged programs of ``models/causal_lm.py``.

(ref: none — the block of recent open sparse-attention mixture-of-experts
decoders.) One layer, for a token's hidden state ``x`` at position ``t``:

- ``a = RMSNorm(x)``; ``q = a.Wq`` (``num_heads`` x ``head_dim``),
  ``k = a.Wk``, ``v = a.Wv`` (``num_kv_heads`` x ``head_dim``); RMSNorm
  per head on q and k; RoPE on q and k.
- indexer: ``qI = a.WqI`` (``indexer_heads`` x ``indexer_head_dim``),
  ``kI = a.WkI`` (one key head), ``w = a.Ww``; RoPE on qI and kI;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` in float32; ``S_t``
  = the ``indexer_topk`` positions ``s <= t`` of largest ``I`` (all of
  them while ``t < indexer_topk``; a tie goes to the lower position).
- attention over ``S_t`` only, query head h on KV head ``h // group``;
  ``x += concat(o).Wo``.
- ``b = RMSNorm(x)``; router softmax over all experts in float32, top-k,
  gates renormalised; ``x += sum_e g_e Wdown_e(silu(Wgate_e b) * Wup_e
  b)``; no token dropped (``ops/moe_ops.py``).
- after the last layer RMSNorm and an UNTIED head.

Variables, the norm, the embedding, the pre-norm layer loop and the head
are ``causal_lm.PreNormStack``'s, shared with ``models/latent_moe_lm.py``;
this module's own are the projections, the indexer and the two programs'
cache reads. Every expert is held here (``RoutedFFN`` without ``held``).

Three paged caches per layer under one page table: K, V and the indexer
key. DECODE gathers the indexer keys of the context, picks positions
(``IndexerTopK``) and reads only those K/V rows (``KVCacheGatherRows``);
PREFILL runs a page-aligned block against the gathered views with the
same selection as a mask (``SparseBlockAttention``). Serving only: there
is no training graph for this block yet (ROADMAP X0).
"""

from __future__ import annotations

import dataclasses

import numpy as np

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.models.causal_lm import (
    CausalLMGenerativeModel, PreNormStack, build_paged_lm_program)
from simple_tensorflow_tpu.platform import monitoring

_moe_imbalance = monitoring.Sampler(
    "/stf/serving/moe_load_imbalance",
    monitoring.ExponentialBuckets(1.0, 1.25, 24),
    "Per decode step: live rows of a routed layer's fullest expert over "
    "the mean per expert, averaged over the layers", "model")
_selected_share = monitoring.Sampler(
    "/stf/serving/sparse_selected_share",
    monitoring.ExponentialBuckets(0.01, 1.5, 12),
    "Per decode step: positions sparse attention reads over the live "
    "context, summed over the step's rows", "model")


@dataclasses.dataclass
class SparseMoEConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    num_experts: int = 128
    experts_per_token: int = 8
    expert_width: int = 768
    norm_topk_prob: bool = True
    indexer_heads: int = 16
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    max_len: int = 262144
    pad_id: int = 0
    eos_id: int = 1

    @staticmethod
    def tiny():
        return SparseMoEConfig(
            vocab_size=96, d_model=64, num_layers=2, num_heads=8,
            num_kv_heads=2, head_dim=16, num_experts=8,
            experts_per_token=2, expert_width=32, indexer_heads=4,
            indexer_head_dim=8, indexer_topk=8, max_len=64)


class _SparseMoEStack(PreNormStack):
    """The block stack as ``build_paged_lm_program`` sees one; variables,
    norm, embedding, the layer loop and the head are
    :class:`PreNormStack`'s."""

    def __init__(self, cfg: SparseMoEConfig, compute_dtype, scope,
                 attn_tile):
        super().__init__(cfg, compute_dtype, scope)
        self.attn_tile = attn_tile

    def layer_caches(self, kvc, total_pages, page_len, sharding):
        cfg = self.cfg
        inner = {"k": (cfg.num_kv_heads, cfg.head_dim),
                 "v": (cfg.num_kv_heads, cfg.head_dim),
                 "ik": (cfg.indexer_head_dim,)}
        return [tuple(
            kvc.kv_cache(f"{self.scope}_pg/l{i}_{kind}", total_pages,
                         page_len, shape, self.compute_dtype,
                         sharding=sharding, paged=True)
            for kind, shape in inner.items())
            for i in range(cfg.num_layers)]

    # -- pieces -----------------------------------------------------------
    def _projections(self, a, lead, positions):
        """q, k, v, indexer q / k / head weights of ``a (rows, d_model)``
        laid out ``lead + (heads, dim)``, normed and rotated."""
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.head_dim
        hi, di = cfg.indexer_heads, cfg.indexer_head_dim

        def heads(name, n, width):
            y = stf.matmul(a, self._w(name, [d, n * width], d))
            return stf.reshape(y, lead + [n, width])

        def rope(x):
            return stf.nn.rotary_embedding(x, positions, cfg.rope_theta)

        q = rope(self._norm(heads("attn/q", cfg.num_heads, hd),
                            "attn/q_norm", hd))
        k = rope(self._norm(heads("attn/k", cfg.num_kv_heads, hd),
                            "attn/k_norm", hd))
        v = heads("attn/v", cfg.num_kv_heads, hd)
        q_idx = rope(heads("indexer/q", hi, di))
        k_idx = stf.reshape(rope(heads("indexer/k", 1, di)), lead + [di])
        w_idx = stf.cast(stf.matmul(a, self._w("indexer/w", [d, hi], d)),
                         stf.float32) * float((hi * di) ** -0.5)
        return q, k, v, q_idx, k_idx, stf.reshape(w_idx, lead + [hi])

    def _attention(self, i, a, rows, lead, positions, attend):
        cfg = self.cfg
        width = cfg.num_heads * cfg.head_dim
        o = attend(i, *self._projections(a, lead, positions))
        o = stf.reshape(o, [rows, width])
        return stf.matmul(o, self._w("attn/out", [width, cfg.d_model],
                                     width))

    def _ffn(self, i, x, row_mask):
        """``x (rows, d_model)`` -> (the routed FFN's output in the
        residual stream's dtype, live rows per expert)."""
        cfg = self.cfg
        d, e, width = cfg.d_model, cfg.num_experts, cfg.expert_width
        b = self._norm(x, "ln2", d, out_dtype="float32")
        y, counts = stf.nn.routed_ffn(
            b, self._w("moe/router", [d, e], d, dtype=stf.float32),
            self._w("moe/gate_up", [e, d, 2 * width], d),
            self._w("moe/down", [e, width, d], width),
            row_mask, top_k=cfg.experts_per_token,
            norm_topk=cfg.norm_topk_prob)
        return stf.cast(y, self.compute_dtype), counts

    # -- the two programs -----------------------------------------------------
    def prefill_block(self, tok, base, cache):
        cfg = self.cfg
        b, s = int(tok.shape[0]), int(tok.shape[1])
        positions = stf.reshape(base, [b, 1]) + stf.constant(
            np.arange(s, dtype=np.int32).reshape(1, s))

        def attend(i, q, k, v, q_idx, k_idx, w_idx):
            with cache.after_append(cache.append(i, k, v, k_idx)):
                views = [cache.gather(i, j) for j in range(3)]
            return stf.nn.sparse_block_attention(
                q, q_idx, w_idx, *views, base, topk=cfg.indexer_topk,
                tile=self.attn_tile)

        with stf.variable_scope(self.scope, reuse=stf.AUTO_REUSE):
            x = stf.reshape(self._embed(tok), [b * s, cfg.d_model])
            x, _ = self._layers(x, b * s, [b, s], positions, attend)
        return x

    def decode_step(self, tok, pos, cache):
        cfg = self.cfg
        b = int(tok.shape[0])
        lengths = pos + 1
        lead = [b, 1]            # appends take (B, P, *inner), P = 1

        def attend(i, q, k, v, q_idx, k_idx, w_idx):
            with cache.after_append(cache.append(i, k, v, k_idx)):
                picked, n_valid = stf.nn.indexer_topk(
                    stf.reshape(q_idx, [b, cfg.indexer_heads,
                                        cfg.indexer_head_dim]),
                    stf.reshape(w_idx, [b, cfg.indexer_heads]),
                    cache.gather(i, 2), lengths, cfg.indexer_topk)
                k_sel = cache.gather_rows(i, 0, picked)
                v_sel = cache.gather_rows(i, 1, picked)
            return stf.nn.selected_attention(
                stf.reshape(q, [b, cfg.num_heads, cfg.head_dim]),
                k_sel, v_sel, n_valid)

        with stf.variable_scope(self.scope, reuse=stf.AUTO_REUSE):
            x, counts = self._layers(
                self._embed(tok), b, lead, stf.reshape(pos, [b, 1]), attend,
                row_mask=cache.live_rows())
            logits = self._logits(x)
        return logits, {"expert_counts": stf.stack(counts)}


def attn_tile_pages(pages_per_seq):
    """Pages of keys one step of prefill attention walks: the largest
    divisor of ``pages_per_seq`` up to 4."""
    return max(n for n in range(1, 5) if pages_per_seq % n == 0)


class SparseMoEGenerativeModel(CausalLMGenerativeModel):
    """Session-owning paged serving programs of the sparse-attention
    routed-FFN decoder; the engine-facing half (``prefill_chunk``,
    ``decode``, ``copy_page``, buckets, page geometry) is
    :class:`CausalLMGenerativeModel`'s, the block stack is this module's.

    ``metrics_label`` (the base class's) labels the two per-step
    samplers (``/stf/serving/moe_load_imbalance``,
    ``sparse_selected_share``): give it the name the model is served
    under. Its attention reads selected rows, not whole pages, so it
    does not sample ``decode_live_page_share``.
    """

    def __init__(self, cfg: SparseMoEConfig, *, pages_per_seq=4, **kw):
        for unsupported in ("int8", "mesh", "tp"):
            if kw.get(unsupported):
                raise ValueError(f"{type(self).__name__} has no "
                                 f"{unsupported}= path")
        super().__init__(cfg, pages_per_seq=pages_per_seq, **kw)

    def _cache_bytes(self):
        cfg = self.cfg
        per_token = (2 * cfg.num_kv_heads * cfg.head_dim
                     + cfg.indexer_head_dim) * cfg.num_layers
        total = (per_token * self.num_pages * self.page_len
                 * self._compute_dtype.size)
        return total, total

    def _build_program(self, *, compute_dtype, scope, tp_axis, **kw):
        stack = _SparseMoEStack(
            self.cfg, compute_dtype, scope,
            attn_tile_pages(self.pages_per_seq) * self.page_len)
        return build_paged_lm_program(stack, compute_dtype=compute_dtype,
                                      scope=scope, **kw)

    def _after_decode(self, out, n, positions):
        counts = np.asarray(out["expert_counts"], np.float64)
        mean = counts.mean(axis=-1)
        if mean.all():
            _moe_imbalance.get_cell(self._metrics_label).add(
                float((counts.max(axis=-1) / mean).mean()))
        context = np.asarray(positions[:n], np.int64) + 1
        _selected_share.get_cell(self._metrics_label).add(
            float(np.minimum(context, self.cfg.indexer_topk).sum()
                  / context.sum()))
