"""Hybrid decoder-only LM — gated delta-rule (KDA) linear attention beside
DeepSeek sparse attention over a NoPE latent cache, a sigmoid-routed SwiGLU
feed-forward with a shared expert, and a hyper-connected residual of
several streams — served through the paged programs of
``models/causal_lm.py`` as ONE CHIP'S SHARE of a deployment that divides
every routed layer's experts over several chips.

(ref: none — the block of GLM-5.3-Flash, ``glm5_next_text``.) ``mixers``
names each layer's token mixer (``K`` KDA, ``D`` sparse latent attention),
``mlps`` its feed-forward (``d`` dense, ``s`` routed); after the last layer
the streams are summed, then an RMSNorm and an UNTIED head.

- RESIDUAL (mHC, ``hc_mult`` = n streams). The embedding is copied to the
  n streams ``X (n, d_model)``; around each sublayer F (the mixer, then the
  feed-forward) ``X <- H_res X + H_post^T F(RMSNorm(H_pre X))`` with the
  per-token coefficients of ``ops/hyper_connection_ops.py`` (``H_res`` a
  Sinkhorn projection, ``hc_sinkhorn_iters`` iterations, ``hc_eps``).
- ``K``, KDA (``num_heads`` x ``head_dim``, ``d_k = d_v``). ``[q | k | v] =
  u W_qkv``, each through a causal depthwise convolution of ``conv_kernel``
  taps (no bias) and SiLU; q and k L2-normalised a head; ``beta =
  sigmoid(u W_beta)`` a head; per key channel ``log alpha =
  gate_lower_bound * sigmoid(exp(A_log) (u W_fa W_fb + dt_bias))`` (rank
  ``gate_rank``, in (gate_lower_bound, 0)); the delta rule of
  ``ops/linear_attention_ops.py`` with ``scale = d_k^-0.5``; ``y = W_o
  [RMSNorm_head(o) * sigmoid(u W_ga W_gb)]``. **A sequence's state is
  ``S`` (heads x d_k x d_v, float32) and the last ``conv_kernel - 1`` rows
  of ``[q | k | v]``: no row a position, so in no page** — two pools a
  layer addressed by SLOT (``kv_cache_ops.StatePool``).
- ``D``, DSA over a NoPE latent cache (``mla_heads``, ``qk_nope_head_dim``,
  ``v_head_dim``, ``q_lora_rank``, ``kv_lora_rank``; no rope part). ``c_q =
  RMSNorm(u W_qa)``, ``q = c_q W_qb``; ``c_kv = RMSNorm(u W_kva)`` is
  the cache row; absorbed attention over it (``q~_h = q_h W_kvb^K_h``,
  scale ``qk_nope_head_dim^-0.5``, ``o_h = (sum p c_kv) W_kvb^V_h``).
  Indexer: ``q_I = c_q W_qI`` (``indexer_heads`` x ``indexer_head_dim``),
  ``k_I = LayerNorm(u W_kI)``, both rotated on their first
  ``indexer_rope_dim`` dimensions in interleaved pairs; ``w = u W_w``. The
  POOLED key of a block of ``index_kpool`` positions is the mean of its
  ``k_I``; ``I[t, b] = sum_j w_j relu(q_I,j . k_b)``; query t reads its own
  block's positions ``<= t`` (the tail, always) and the ``indexer_topk /
  index_kpool - 1`` complete blocks before it of largest ``I`` (a tie to
  the lower block; all of them while there are no more)
  (``ops/sparse_attention_ops.py``). Two paged caches under the page
  table: the latent rows, and the pooled keys — float32, a page holding
  ``page_len / index_kpool`` of them, the running SUM of a block's keys
  over ``index_kpool`` (complete, its mean): a decode step adds its key to
  the row of its block.
- ``d``: ``W_down(swiglu(W_gate b, W_up b))`` of width ``dense_width``;
  ``s``: ``s = sigmoid(b W_r)`` over ALL ``num_experts`` in float32, the
  ``experts_per_token`` of largest ``s + bias`` (the bias chooses, it
  does not weigh; a tie to the lower expert), gates ``s`` over their sum x
  ``routed_scaling_factor``, every expert a SwiGLU of ``expert_width``,
  plus a shared expert of ``shared_width``, ungated. Every SwiGLU clamps
  ``gate <- min(gate, swiglu_limit)``, ``up <- clip(up, +-swiglu_limit)``.

THE SHARE. ``held_experts = (first, count)`` and ``vocab_size`` as
``models/latent_moe_lm.py`` has them: this chip's experts' part of every
routed sum, the shared expert added once, a slice of the vocabulary; the
mixers whole.

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
from simple_tensorflow_tpu.models.sparse_moe_lm import _selected_share
from simple_tensorflow_tpu.models.state_space_moe_lm import (
    _state_bytes_share, _state_pool_bytes)

# the published layer_types / mlp_layer_types, one character a layer
GLM_MIXERS = "KKKD" * 11 + "K"
GLM_MLPS = "ddd" + "s" * 42


@dataclasses.dataclass
class HybridLinearMoEConfig:
    vocab_size: int = 154880
    d_model: int = 4096
    mixers: str = GLM_MIXERS
    mlps: str = GLM_MLPS
    num_heads: int = 64
    head_dim: int = 128
    conv_kernel: int = 4
    gate_lower_bound: float = -5.0
    gate_rank: int = 128
    kda_chunk: int = 64
    mla_heads: int = 64
    qk_nope_head_dim: int = 256
    v_head_dim: int = 256
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    indexer_heads: int = 32
    indexer_head_dim: int = 128
    indexer_rope_dim: int = 64
    indexer_rope_theta: float = 10000.0
    indexer_topk: int = 2048
    index_kpool: int = 4
    dense_width: int = 12288
    num_experts: int = 288
    experts_per_token: int = 8
    expert_width: int = 2048
    shared_width: int = 2048
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    swiglu_limit: float = 10.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    rms_norm_eps: float = 1e-5
    # (first, count) of the experts this chip holds; None = all of them
    held_experts: tuple | None = None
    max_len: int = 1048576
    pad_id: int = 0
    eos_id: int = 1

    @staticmethod
    def tiny():
        return HybridLinearMoEConfig(
            vocab_size=96, d_model=64, mixers="KDKK", mlps="dsss",
            num_heads=4, head_dim=16, gate_rank=8, kda_chunk=4,
            mla_heads=4, qk_nope_head_dim=16, v_head_dim=16, q_lora_rank=32,
            kv_lora_rank=32, indexer_heads=4, indexer_head_dim=16,
            indexer_rope_dim=8, indexer_topk=12, dense_width=96,
            num_experts=16, experts_per_token=4, expert_width=32,
            shared_width=32, hc_sinkhorn_iters=6, held_experts=(4, 8),
            max_len=64)

    def __post_init__(self):
        if len(self.mixers) != len(self.mlps) or set(self.mixers) - {
                "K", "D"} or set(self.mlps) - {"d", "s"}:
            raise ValueError(f"mixers {self.mixers!r} and mlps "
                             f"{self.mlps!r}: one K/D and one d/s a layer")

    @property
    def num_layers(self):
        return len(self.mixers)

    @property
    def layer_kinds(self):
        return tuple(zip(self.mixers, self.mlps))

    @property
    def held(self):
        return tuple(self.held_experts or (0, self.num_experts))

    @property
    def kda_width(self):
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self):
        return 3 * self.kda_width

    @property
    def state_shape(self):
        """Inner shape of a KDA layer's state pool: ``(heads, d_k, d_v)``,
        the key channels on the sublanes
        (``ops/pallas/kda_state_update.py``)."""
        return (self.num_heads, self.head_dim, self.head_dim)

    def state_bytes_per_slot(self, compute_dtype_size):
        """One sequence's state over all ``K`` layers: float32 ``S`` and
        the convolution's carried window in the compute dtype."""
        per_layer = (4 * int(np.prod(self.state_shape))
                     + (self.conv_kernel - 1) * self.conv_dim
                     * compute_dtype_size)
        return self.mixers.count("K") * per_layer

    def page_bytes_per_token(self, compute_dtype_size):
        """A position's bytes in the paged pools of all ``D`` layers: its
        latent row, and its share of its block's float32 pooled key."""
        return self.mixers.count("D") * (
            self.kv_lora_rank * compute_dtype_size
            + 4 * self.indexer_head_dim / self.index_kpool)

    @property
    def softmax_scale(self):
        return self.qk_nope_head_dim ** -0.5


class _HybridLinearMoEStack(PreNormStack):
    """The block stack as ``build_paged_lm_program`` sees one: a mixer and
    a feed-forward a layer, by ``cfg.layer_kinds``, around the
    hyper-connected residual."""

    keeps_state = True

    def __init__(self, cfg, compute_dtype, scope, page_len):
        super().__init__(cfg, compute_dtype, scope)
        # key positions a step of prefill's latent attention walks: a
        # step reads and writes the (rows, heads, page_len, kv_lora_rank)
        # float32 accumulator (268 MB at 4 x 64 x 512 x 512), so fewer,
        # wider steps (my chip run, PR 39: 128-key steps cost 39 ms a call)
        self.attn_tile = page_len

    def layer_caches(self, kvc, total_pages, page_len, sharding,
                     state_slots):
        cfg = self.cfg

        def of(i, mixer):
            name = f"{self.scope}_pg/l{i}_"
            if mixer == "K":
                return (kvc.state_pool(name + "S", state_slots,
                                       cfg.state_shape, stf.float32),
                        kvc.state_pool(name + "conv", state_slots,
                                       ((cfg.conv_kernel - 1)
                                        * cfg.conv_dim,),
                                       self.compute_dtype))
            return (kvc.kv_cache(name + "latent", total_pages, page_len,
                                 (cfg.kv_lora_rank,), self.compute_dtype,
                                 sharding=sharding, paged=True),
                    kvc.kv_cache(name + "ikey", total_pages,
                                 page_len // cfg.index_kpool,
                                 (cfg.indexer_head_dim,), stf.float32,
                                 sharding=sharding, paged=True))

        return [of(i, m) for i, m in enumerate(cfg.mixers)]

    # -- pieces -----------------------------------------------------------
    def _vec(self, name, width, init):
        return stf.get_variable(name, [width], dtype=stf.float32,
                                initializer=init)

    def _low_rank(self, u, name, width):
        cfg = self.cfg
        a = stf.matmul(u, self._w(name + "_a", [cfg.d_model, cfg.gate_rank],
                                  cfg.d_model))
        return stf.cast(stf.matmul(a, self._w(name + "_b",
                                              [cfg.gate_rank, width],
                                              cfg.gate_rank)), stf.float32)

    @staticmethod
    def _l2norm(x):
        x = stf.cast(x, stf.float32)
        return x * stf.rsqrt(stf.reduce_sum(stf.square(x), axis=-1,
                                            keepdims=True) + 1e-6)

    def _swiglu(self, b, name, width):
        d = self.cfg.d_model
        gate, up = stf.split(
            stf.matmul(b, self._w(f"{name}gate_up", [d, 2 * width], d)),
            2, axis=-1)
        lim = self.cfg.swiglu_limit
        h = stf.nn.silu(stf.minimum(gate, lim)) * stf.clip_by_value(
            up, -lim, lim)
        return stf.matmul(h, self._w(f"{name}down", [width, d], width))

    # -- the two mixers ---------------------------------------------------
    def _kda(self, i, u, rows, lead, positions, cache, block):
        cfg = self.cfg
        d, h, dk, w = cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.kda_width
        taps, f32 = cfg.conv_kernel, stf.float32
        s_pool, conv_pool = cache.state(i)
        slots, fresh = cache.slots, cache.fresh()
        # block: (B, S, ...) with the rows' real lengths; else (B, ...)
        shape = lead if block else lead[:1]
        lens = (cache.lens,) if block else ()
        qkv = stf.nn.silu(stf.nn.causal_conv1d(
            stf.reshape(stf.matmul(u, self._w("kda/qkv", [d, 3 * w], d)),
                        shape + [3 * w]),
            self._w("kda/conv_w", [taps, 3 * w], taps),
            stf.zeros([3 * w], f32), conv_pool, slots, fresh, *lens))
        q, k, v = (stf.reshape(t, shape + [h, dk])
                   for t in stf.split(qkv, 3, axis=-1))
        q, k, v = self._l2norm(q), self._l2norm(k), stf.cast(v, f32)
        beta = stf.reshape(stf.sigmoid(stf.cast(stf.matmul(
            u, self._w("kda/beta", [d, h], d)), f32)), shape + [h])
        a = stf.exp(self._vec("kda/A_log", h, stf.zeros_initializer()))
        f = stf.reshape(self._low_rank(u, "kda/f", w) + self._vec(
            "kda/dt_bias", w, stf.zeros_initializer()), shape + [h, dk])
        g = cfg.gate_lower_bound * stf.sigmoid(
            stf.reshape(a, [h, 1]) * f)
        scale = dk ** -0.5
        if block:
            o = stf.nn.kda_chunk_scan(q, k, v, g, beta, s_pool, slots, fresh,
                                      cache.lens, chunk=cfg.kda_chunk,
                                      scale=scale)
        else:
            o = stf.nn.kda_state_update(q, k, v, g, beta, s_pool, slots,
                                        fresh, scale=scale)
        o = self._norm(o, "kda/o_norm", dk) * stf.reshape(
            stf.sigmoid(self._low_rank(u, "kda/g", w)), shape + [h, dk])
        return stf.matmul(stf.cast(stf.reshape(o, [rows, w]),
                                   self.compute_dtype),
                          self._w("kda/out", [w, d], w))

    def _indexer(self, u, c_q, lead, positions):
        """``q_I (lead, Hi, Di)``, ``k_I (lead, Di)``, ``w (lead, Hi)``
        float32, rotated."""
        cfg = self.cfg
        d, qr = cfg.d_model, cfg.q_lora_rank
        hi, di, dr = (cfg.indexer_heads, cfg.indexer_head_dim,
                      cfg.indexer_rope_dim)
        f32 = stf.float32

        def rope(x):
            head, rest = stf.split(x, [dr, di - dr], axis=-1)
            head = stf.nn.rotary_embedding(head, positions,
                                           cfg.indexer_rope_theta,
                                           interleave=True)
            return stf.concat([head, rest], axis=-1)

        q_i = rope(stf.reshape(stf.cast(stf.matmul(
            c_q, self._w("indexer/q", [qr, hi * di], qr)), f32),
            lead + [hi, di]))
        k_i = stf.cast(stf.matmul(u, self._w("indexer/k", [d, di], d)), f32)
        mean = stf.reduce_mean(k_i, axis=-1, keepdims=True)
        var = stf.reduce_mean(stf.square(k_i - mean), axis=-1,
                              keepdims=True)
        k_i = ((k_i - mean) * stf.rsqrt(var + 1e-6)
               * self._vec("indexer/k_norm", di, stf.ones_initializer())
               + self._vec("indexer/k_bias", di, stf.zeros_initializer()))
        k_i = stf.reshape(rope(stf.reshape(k_i, lead + [1, di])),
                          lead + [di])
        w_i = stf.reshape(stf.cast(stf.matmul(
            u, self._w("indexer/w", [d, hi], d)), f32), lead + [hi])
        return q_i, k_i, w_i

    def _dsa(self, i, u, rows, lead, positions, cache, block):
        cfg = self.cfg
        d, h = cfg.d_model, cfg.mla_heads
        nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
        qr, kr, pool = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.index_kpool
        c_q = self._norm(stf.matmul(u, self._w("attn/q_a", [d, qr], d)),
                         "attn/q_norm", qr)
        q = stf.reshape(stf.matmul(c_q, self._w("attn/q_b", [qr, h * nope],
                                                qr)), lead + [h, nope])
        c_kv = self._norm(stf.matmul(u, self._w("attn/kv_a", [d, kr], d)),
                          "attn/kv_norm", kr)
        w_k, w_v = stf.split(
            stf.reshape(self._w("attn/kv_b", [kr, h * (nope + vd)], kr),
                        [kr, h, nope + vd]), [nope, vd], axis=-1)
        q_abs = stf.einsum("bshn,chn->bshc", q, w_k)
        q_i, k_i, w_i = self._indexer(u, c_q, lead, positions)
        row = stf.reshape(c_kv, lead + [kr])
        if block:
            b, s = lead
            real = stf.cast(stf.less(
                stf.constant(np.arange(s, dtype=np.int32).reshape(1, s)),
                stf.reshape(cache.lens, [b, 1])), stf.float32)
            pooled = stf.reduce_sum(stf.reshape(
                k_i * stf.expand_dims(real, -1),
                [b, s // pool, pool, cfg.indexer_head_dim]), axis=2) / pool
            with cache.after_append([
                    cache.append_one(i, 0, row),
                    cache.append_one(i, 1, pooled,
                                     cache.offsets // pool)]):
                view, pooled_view = cache.gather(i, 0), cache.gather(i, 1)
            chosen = stf.nn.pooled_block_choice(
                q_i, w_i, pooled_view, cache.base, topk=cfg.indexer_topk,
                pool=pool)
            out = stf.nn.sparse_latent_block_attention(
                q_abs, chosen, view, cache.base, pool=pool,
                sm_scale=cfg.softmax_scale, value_dim=kr,
                tile=self.attn_tile)
        else:
            b = lead[0]
            pos = cache.base
            block_of = stf.reshape(pos // pool, [b, 1])
            # the running sum of this position's block: a block's first
            # position starts it
            held = stf.reshape(cache.gather_rows(i, 1, block_of),
                               [b, 1, cfg.indexer_head_dim])
            first = stf.reshape(stf.equal(pos % pool, 0), [b, 1, 1])
            pooled = stf.where(first, stf.zeros_like(held), held) + k_i / pool
            with cache.after_append([
                    cache.append_one(i, 0, row),
                    cache.append_one(i, 1, pooled,
                                     cache.offsets // pool)]):
                picked, n_valid = stf.nn.indexer_topk(
                    stf.reshape(q_i, [b, cfg.indexer_heads,
                                      cfg.indexer_head_dim]),
                    stf.reshape(w_i, [b, cfg.indexer_heads]),
                    cache.gather(i, 1), pos + 1, cfg.indexer_topk,
                    pool=pool)
                selected = cache.gather_rows(i, 0, picked)
            out = stf.nn.selected_latent_attention(
                stf.reshape(q_abs, [b, h, kr]), selected, n_valid,
                sm_scale=cfg.softmax_scale)
        o = stf.einsum("bshc,chv->bshv", stf.reshape(out, lead + [h, kr]),
                       w_v)
        return stf.matmul(stf.reshape(o, [rows, h * vd]),
                          self._w("attn/out", [h * vd, d], h * vd))

    # -- the two feed-forwards ---------------------------------------------
    def _mlp(self, kind, h, row_mask):
        cfg = self.cfg
        d = cfg.d_model
        if kind == "d":
            return self._swiglu(self._norm(h, "ln2", d), "ffn/",
                                cfg.dense_width), None
        e, width = cfg.num_experts, cfg.expert_width
        held = cfg.held
        b = self._norm(h, "ln2", d, out_dtype="float32")
        y, counts = stf.nn.routed_ffn(
            b, self._w("moe/router", [d, e], d, dtype=stf.float32),
            self._w("moe/gate_up", [held[1], d, 2 * width], d),
            self._w("moe/down", [held[1], width, d], width),
            row_mask, top_k=cfg.experts_per_token,
            norm_topk=cfg.norm_topk_prob, score="sigmoid",
            bias=stf.get_variable(
                "moe/bias", [e], dtype=stf.float32,
                initializer=stf.random_normal_initializer(stddev=0.01)),
            gate_scale=cfg.routed_scaling_factor, held=held,
            swiglu_limit=cfg.swiglu_limit)
        # the shared expert: once, whatever is held here
        shared = self._swiglu(stf.cast(b, self.compute_dtype), "moe/shared_",
                              cfg.shared_width)
        return stf.cast(y + stf.cast(shared, stf.float32),
                        self.compute_dtype), counts

    def _layer(self, kind, i, x, rows, lead, positions, attend, row_mask):
        cfg = self.cfg
        cache, block = attend
        mixer = {"K": self._kda, "D": self._dsa}[kind[0]]
        x = self._hyper(x, "hc_attn", lambda h: mixer(
            i, self._norm(h, "ln1", cfg.d_model), rows, lead, positions,
            cache, block))
        counts = []

        def ffn(h):
            y, c = self._mlp(kind[1], h, row_mask)
            counts.append(c)
            return y

        return self._hyper(x, "hc_ffn", ffn), counts[0]

    # -- the two programs -----------------------------------------------------
    def prefill_block(self, tok, base, cache):
        cfg = self.cfg
        b, s = int(tok.shape[0]), int(tok.shape[1])
        positions = stf.reshape(base, [b, 1]) + stf.constant(
            np.arange(s, dtype=np.int32).reshape(1, s))
        with stf.variable_scope(self.scope, reuse=stf.AUTO_REUSE):
            x = self._streams(stf.reshape(self._embed(tok),
                                          [b * s, cfg.d_model]))
            x, _ = self._layers(x, b * s, [b, s], positions, (cache, True))
        return x

    def decode_step(self, tok, pos, cache):
        b = int(tok.shape[0])
        with stf.variable_scope(self.scope, reuse=stf.AUTO_REUSE):
            x, counts = self._layers(
                self._streams(self._embed(tok)), b, [b, 1],
                stf.reshape(pos, [b, 1]), (cache, False),
                row_mask=cache.live_rows())
            logits = self._logits(self._merged(x))
        # (a cut without a routed layer has no histogram to return)
        return logits, ({"expert_counts": stf.stack(counts)} if counts
                        else {})


class HybridLinearMoEGenerativeModel(CausalLMGenerativeModel):
    """Session-owning paged serving programs of the hybrid linear-attention
    routed-FFN decoder; the engine-facing half is
    :class:`CausalLMGenerativeModel`'s — with ``slots`` and ``lens``, since
    ``state_outside_pages`` — the block stack is this module's.

    ``metrics_label`` (the base class's) labels the gauge
    ``/stf/serving/state_pool_bytes`` and the per-step samplers:
    ``state_bytes_share``, ``sparse_selected_share`` (positions the
    sparse layers read over the live context), ``moe_load_imbalance`` over
    the HELD experts and ``moe_local_pair_share``. Its attention reads
    selected rows, not whole pages: no ``decode_live_page_share``.
    """

    def __init__(self, cfg: HybridLinearMoEConfig, *, pages_per_seq=4,
                 **kw):
        for unsupported in ("int8", "mesh", "tp"):
            if kw.get(unsupported):
                raise ValueError(f"{type(self).__name__} has no "
                                 f"{unsupported}= path")
        page_len = kw.get("page_len", 8)
        if page_len % cfg.index_kpool:
            raise ValueError(f"page_len {page_len} is no whole number of "
                             f"pooled blocks of {cfg.index_kpool}")
        super().__init__(cfg, pages_per_seq=pages_per_seq, **kw)
        _state_pool_bytes.get_cell(self._metrics_label).set(
            self._state_pool_bytes())

    def _state_pool_bytes(self):
        return (self.num_slots + 1) * self.cfg.state_bytes_per_slot(
            self._compute_dtype.size)

    def _cache_bytes(self):
        """Both kinds of pool: latent rows and pooled keys by page, state
        by slot."""
        pages = int(self.cfg.page_bytes_per_token(self._compute_dtype.size)
                    * self.num_pages * self.page_len)
        total = pages + self._state_pool_bytes()
        return total, total

    def _build_program(self, *, compute_dtype, scope, tp_axis, **kw):
        return build_paged_lm_program(
            _HybridLinearMoEStack(self.cfg, compute_dtype, scope,
                                  kw["page_len"]),
            compute_dtype=compute_dtype, scope=scope, **kw)

    def _after_decode(self, out, n, positions):
        cfg, size = self.cfg, self._compute_dtype.size
        context = np.asarray(positions[:n], np.int64) + 1
        live_pages = -(-context // self.page_len)
        state = n * cfg.state_bytes_per_slot(size)
        pages = (live_pages.sum() * self.page_len
                 * cfg.page_bytes_per_token(size))
        label = self._metrics_label
        _state_bytes_share.get_cell(label).add(
            float(state / (state + pages)))
        if "D" in cfg.mixers:
            # the tail block's positions <= t and the chosen blocks'
            read = np.minimum(context, cfg.indexer_topk
                              - cfg.index_kpool + 1 + (context - 1)
                              % cfg.index_kpool)
            _selected_share.get_cell(label).add(
                float(read.sum() / context.sum()))
        if "expert_counts" in out:
            sample_held_expert_counts(label, out["expert_counts"], n,
                                      cfg.experts_per_token)
