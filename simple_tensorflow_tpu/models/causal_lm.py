"""Decoder-only causal LM: the shared-prefix serving flagship.

(ref: the reference's seq2seq decoder stack minus the encoder — GPT-style
next-token LM over one token stream.)

Two halves:

- :func:`causal_lm_logits` / :func:`causal_lm_train_model`: the training
  graph. Layer-for-layer this is the transformer DECODER with the
  cross-attention sublayer removed — the sublayer/LN naming (``ln1``
  after self-attention, ``ln3`` after the FFN, no ``ln2``) deliberately
  matches what ``transformer._incremental_decode`` builds when
  ``cross_kv=None``, so ONE checkpoint serves both the train graph and
  the incremental serving programs below.

- :func:`build_causal_lm_program` / :class:`CausalLMGenerativeModel`:
  the PAGED serving programs. Where the seq2seq serving model keys
  caches by (slot, position) with one row per live sequence, the causal
  LM keys them by PAGE: each cache is ``(num_pages + 1, page_len, H,
  hd)`` with ``paged=True``, a sequence's KV state is the ordered page
  list in its page table, and attention reads K and V pages from the
  pool through that table (``PagedDecodeAttention``; no logical view).
  That indirection is what the shared-prefix prompt cache
  (serving/prefix_cache.py) needs: two sequences whose prompts share a
  prefix point their leading page-table entries at the SAME physical
  pages (refcounted), prefill runs once, and divergence copies a page
  (``KVCachePageCopy``) before private appends — copy-on-write.
"""

from __future__ import annotations

import contextlib

import numpy as np

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.client.session import materialize
from simple_tensorflow_tpu.models import common
from simple_tensorflow_tpu.platform import monitoring
from simple_tensorflow_tpu.models.transformer import (
    TransformerConfig, _attention, _block_decode, _dense, _embed, _ffn,
    _incremental_decode, _ln, _residual, _tp_gather,
    build_int8_logits_weights, decode_tp_collective_bytes,
    decode_tp_partition_rules, generative_cache_bytes, resolve_decode_tp,
    smoothed_xent)

_live_page_share = monitoring.Sampler(
    "/stf/serving/decode_live_page_share",
    monitoring.ExponentialBuckets(0.01, 1.5, 12),
    "Per decode step: page-table entries that hold a live page, summed "
    "over the step's rows, over rows x pages_per_seq: the share of the "
    "table paged decode attention reads", "model")

_prefill_pad_tokens = monitoring.Counter(
    "/stf/serving/prefill_pad_tokens",
    "Pad tokens the prefill programs carried behind the real tokens of "
    "their page-chunk rows (a prompt's last chunk is padded to the page): "
    "what a model with state outside its pages masks by ``lens``", "model")
_prefill_real_tokens = monitoring.Counter(
    "/stf/serving/prefill_real_tokens",
    "Real prompt tokens the prefill programs carried in their page-chunk "
    "rows (beside /stf/serving/prefill_pad_tokens)", "model")
_prefill_pad_share = monitoring.Sampler(
    "/stf/serving/prefill_pad_share",
    monitoring.ExponentialBuckets(0.001, 2.0, 11),
    "Per prefill_chunk of a model with state outside its pages: the pad "
    "tokens over all the tokens of the page-chunk rows it was handed",
    "model")

_prefill_call_rows = monitoring.Sampler(
    "/stf/serving/prefill_call_rows",
    monitoring.ExponentialBuckets(1.0, 2.0, 8),
    "Per prefill program call: the real (non-pad) page-chunk rows it "
    "carried; count is the calls, sum / count the rows a call", "model")

# the causal LM reuses TransformerConfig (decoder-side fields only:
# d_model/num_heads/d_ff/num_layers/dropout/vocab/max_len)
CausalLMConfig = TransformerConfig


def causal_lm_logits(ids, cfg: TransformerConfig, training=True,
                     compute_dtype=stf.bfloat16, scope="causal_lm",
                     recompute=False):
    """Next-token logits (B, S, vocab) for token ids (B, S).

    Decoder-only stack: causal flash self-attention + FFN per layer,
    tied-embedding softmax. Position ``j``'s logits predict token
    ``j+1``.
    """
    with stf.variable_scope(scope, reuse=stf.AUTO_REUSE):
        h, emb = _embed(ids, cfg, compute_dtype, training)
        with stf.variable_scope("decoder"):
            def lm_layer(hh, i):
                with stf.variable_scope(f"layer_{i}"):
                    a = _attention(hh, hh, None, cfg, training,
                                   compute_dtype, "self_attn",
                                   causal=True)
                    hh = _ln(_residual(a, hh, cfg, training), cfg, "ln1")
                    f = _ffn(hh, cfg, training, "ffn")
                    # ln3, not ln2: the serving step (cross-skipped
                    # _incremental_decode) reuses these variables by name
                    return _ln(hh + f, cfg, "ln3")

            for i in range(cfg.num_layers):
                h = common.maybe_recompute(lm_layer, h, i, recompute,
                                           "lm")
        b, s = int(ids.shape[0]), int(ids.shape[1])
        flat = stf.reshape(h, [b * s, cfg.d_model])
        logits = stf.matmul(flat, stf.cast(emb, h.dtype.base_dtype),
                            transpose_b=True)
        return stf.reshape(logits, [b, s, cfg.vocab_size])


def causal_lm_train_model(batch_size=8, seq_len=32,
                          cfg: TransformerConfig | None = None,
                          learning_rate=1.0, warmup_steps=4000,
                          compute_dtype=stf.bfloat16, recompute=False):
    """Training graph: tok_in/tok_out -> label-smoothed LM loss -> Adam
    with the noam schedule (same recipe as the seq2seq transformer)."""
    cfg = cfg or TransformerConfig.base()
    tok_in = stf.placeholder(stf.int32, [batch_size, seq_len], "tok_in")
    tok_out = stf.placeholder(stf.int32, [batch_size, seq_len], "tok_out")
    logits = causal_lm_logits(tok_in, cfg, training=True,
                              compute_dtype=compute_dtype,
                              recompute=recompute)
    weights = stf.cast(stf.not_equal(tok_out, cfg.pad_id), stf.float32)
    loss = smoothed_xent(logits, tok_out, weights, cfg)
    gs = stf.train.get_or_create_global_step()
    step = stf.cast(gs, stf.float32) + 1.0
    lr = (learning_rate * cfg.d_model ** -0.5 *
          stf.minimum(stf.pow(step, -0.5), step * warmup_steps ** -1.5))
    opt = stf.train.AdamOptimizer(lr, beta1=0.9, beta2=0.997,
                                  epsilon=1e-9)
    train_op = opt.minimize(loss, global_step=gs)
    return {"tok_in": tok_in, "tok_out": tok_out, "loss": loss,
            "train_op": train_op, "learning_rate": lr, "global_step": gs}


# ---------------------------------------------------------------------------
# Paged serving programs
# ---------------------------------------------------------------------------

class _PagedCaches:
    """Cache accessor for the paged decode/prefill programs, the
    page-table counterpart of ``transformer._SlotCaches``.

    Appends land at ``(dst_pages[b], offsets[b] + j)`` — ONE physical
    page per sequence per step/block — while attention reads the
    sequence's full history, across however many (possibly shared)
    pages it spans, through ``page_tables (B, n_blocks)``: K and V
    pages straight from the stored pools (``PagedDecodeAttention``),
    or the one pool of latent rows a latent-attention layer keeps
    (``PagedLatentAttention``); no logical view gathered. The RAW between a layer's appends and its
    read is ordered by an explicit control dependency (the appended
    page is always present in the table).

    A layer's pools are of either kind: paged ``KVCache``s, read through
    the table, or ``StatePool``s addressed by SLOT (``state``): what a
    recurrent layer carries a sequence. A stack that keeps any gets
    ``slots (B,)``, and in prefill ``lens (B,)``, the real tokens of each
    row's chunk; ``fresh`` says which rows start a sequence (position 0):
    their state is zero whatever the slot last held."""

    def __init__(self, caches, page_tables, dst_pages, offsets, base,
                 slots=None, lens=None):
        self._caches = caches        # [tuple of KVCache | StatePool] per layer
        self._tables = page_tables   # (B, n_blocks) int32
        self._dst = dst_pages        # (B,) int32 physical page written
        self._off = offsets          # (B,) int32 in-page start offset
        self._base = base            # (B,) int32 committed length BEFORE
        self.slots = slots           # (B,) int32 state-pool rows, or None
        self.lens = lens             # (B,) int32 real tokens a row (prefill)

    def _attend(self, layer, q, k_new, v_new, lengths, causal_offset):
        kc, vc = self._caches[layer]
        with self.after_append(self.append(layer, k_new, v_new)):
            return stf.nn.paged_decode_attention(
                q, kc, vc, self._tables, lengths,
                causal_offset=causal_offset)

    def attend(self, layer, q, k_new, v_new):
        """One decode position: ``q (B, H, D)`` over the history with
        the new row appended (``transformer._incremental_decode``)."""
        return self._attend(layer, q, k_new, v_new, self._base + 1, False)

    def attend_block(self, layer, q, k_new, v_new):
        """A page-aligned block: ``q (B, Kq, H, D)``, query j sees the
        committed prefix plus block positions <= j
        (``transformer._block_decode``)."""
        return self._attend(layer, q, k_new, v_new, self._base, True)

    def attend_latent(self, layer, q, row_new, *, value_dim, sm_scale,
                      block=False):
        """A layer whose ONE cache holds latent rows (``PagedLatent
        Attention``): append ``row_new (B, P, W)``, then ``q (B, H, W)``
        — or with ``block`` a page-aligned ``(B, Kq, H, W)`` — over the
        history, each head against the same rows."""
        cache, = self._caches[layer]
        with self.after_append(self.append(layer, row_new)):
            return stf.nn.paged_latent_attention(
                q, cache, self._tables,
                self._base if block else self._base + 1,
                value_dim=value_dim, sm_scale=sm_scale, causal_offset=block)

    # a stack that keeps more than K and V per layer, or reads selected
    # rows instead of the whole history, appends first and reads under
    # ``after_append``'s control dependency
    def append(self, layer, *new):
        return [c.append(n, self._dst, self._off)
                for c, n in zip(self._caches[layer], new)]

    def append_one(self, layer, which, new, offsets=None):
        """One cache of the layer's group, at ``offsets`` into each row's
        page — a cache whose page holds fewer rows than positions (one a
        block of them) — or the positions' own."""
        return self._caches[layer][which].append(
            new, self._dst, self._off if offsets is None else offsets)

    @property
    def offsets(self):
        """(B,) int32: where each row's appends start in its page."""
        return self._off

    @property
    def base(self):
        """(B,) int32: each row's committed length before this call."""
        return self._base

    @staticmethod
    def after_append(appended):
        return stf.control_dependencies([t.op for t in appended])

    def gather(self, layer, which):
        return self._caches[layer][which].gather(self._tables)

    def gather_rows(self, layer, which, positions):
        return self._caches[layer][which].gather_rows(self._tables,
                                                      positions)

    def state(self, layer):
        """The ``StatePool``s of a layer that keeps state by slot."""
        return self._caches[layer]

    def fresh(self):
        """(B,) bool: the rows at position 0, whose sequence starts."""
        return stf.equal(self._base, 0)

    def live_rows(self):
        """(B,) bool: the rows that write a real page. A bucket's padding
        rows write the scratch page, the pool's last."""
        flat = [c for group in self._caches for c in group]
        paged = next((c for c in flat if c.paged), None)
        if paged is None:       # a stack of state pools alone: by slot
            return stf.not_equal(self.slots, flat[0].scratch_slot)
        return stf.not_equal(self._dst, paged.stored_shape[0] - 1)


class _PostLNStack:
    """The post-LN decoder-only stack of :func:`causal_lm_logits` as the
    paged builder sees a block stack: which caches a layer keeps, one
    page-aligned prompt block, one decode position with its logits."""

    def __init__(self, cfg: TransformerConfig, compute_dtype, scope,
                 int8=False, tp_axis=None):
        self.cfg, self.scope, self.tp_axis = cfg, scope, tp_axis
        self.compute_dtype, self.int8 = compute_dtype, int8
        self.vocab_size, self.max_positions = cfg.vocab_size, cfg.max_len
        self.int8_init = self._wq = self._w_scale = None

    def layer_caches(self, kvc, total_pages, page_len, sharding):
        heads = self.cfg.num_heads
        inner = (heads, self.cfg.d_model // heads)
        return [tuple(
            kvc.kv_cache(f"{self.scope}_pg/l{i}_{kind}", total_pages,
                         page_len, inner, self.compute_dtype,
                         sharding=sharding, paged=True)
            for kind in "kv") for i in range(self.cfg.num_layers)]

    def prefill_block(self, tok, base, cache):
        h, _ = _block_decode(tok, base, cache, None, None, None, self.cfg,
                             self.compute_dtype, self.scope,
                             tp_axis=self.tp_axis)
        return h

    def decode_step(self, tok, pos, cache):
        h, emb = _incremental_decode(tok, pos, cache, None, None, None,
                                     self.cfg, self.compute_dtype,
                                     self.scope, tp_axis=self.tp_axis)
        if self.int8:
            if self.int8_init is None:
                self._wq, self._w_scale, self.int8_init = \
                    build_int8_logits_weights(emb, self.cfg,
                                              scope=self.scope)
            logits = stf.nn.quantized_matmul(h, self._wq, self._w_scale)
        else:
            logits = stf.matmul(h, stf.cast(emb, h.dtype.base_dtype),
                                transpose_b=True)
        return _tp_gather(stf.cast(logits, stf.float32), self.tp_axis), {}


class PreNormStack:
    """What the pre-norm RMSNorm stacks share (``models/sparse_moe_lm.py``,
    ``models/latent_moe_lm.py``, ``models/state_space_moe_lm.py``,
    ``models/hybrid_linear_moe_lm.py``, ``models/short_conv_moe_lm.py``):
    variables, the norm, the embedding, a loop over the layers' KINDS and
    the head, untied or tied to the embedding. A stack gives
    ``layer_caches``, ``prefill_block``, ``decode_step`` (the builder's
    contract). ``layer_kinds`` names each
    layer's kind; the default, ``"block"``, is ``x += attention(norm(x));
    x += ffn(x)`` over the stack's own ``_attention(i, a, rows, lead,
    positions, attend)`` -> ``(rows, d_model)`` and ``_ffn(i, x,
    row_mask)`` -> ``(y, expert counts or None)``; a stack with other
    kinds (one mixer a layer) gives its own ``_layer``."""

    def __init__(self, cfg, compute_dtype, scope):
        self.cfg, self.scope = cfg, scope
        self.compute_dtype = compute_dtype
        self.vocab_size, self.max_positions = cfg.vocab_size, cfg.max_len

    def _w(self, name, shape, fan_in, dtype=None):
        return stf.get_variable(
            name, shape, dtype=dtype or self.compute_dtype,
            initializer=stf.random_normal_initializer(
                stddev=fan_in ** -0.5))

    def _norm(self, x, name, width, out_dtype=None, eps=None):
        gamma = stf.get_variable(name, [width], dtype=stf.float32,
                                 initializer=stf.ones_initializer())
        return stf.nn.rms_norm(
            x, gamma, eps=self.cfg.rms_norm_eps if eps is None else eps,
            out_dtype=out_dtype)

    def _embedding(self):
        cfg = self.cfg
        return stf.get_variable(
            "embed", [cfg.vocab_size, cfg.d_model],
            dtype=self.compute_dtype,
            initializer=stf.random_normal_initializer(stddev=1.0))

    def _embed(self, tok):
        return stf.gather(self._embedding(), tok)

    @property
    def layer_kinds(self):
        return getattr(self.cfg, "layer_kinds", None) or (
            ("block",) * self.cfg.num_layers)

    def _layer(self, kind, i, x, rows, lead, positions, attend, row_mask):
        """Layer ``i`` of ``kind``: ``(x, expert counts or None)``."""
        if kind != "block":
            raise ValueError(f"{type(self).__name__} has no layer kind "
                             f"{kind!r}")
        a = self._norm(x, "ln1", self.cfg.d_model)
        x = x + self._attention(i, a, rows, lead, positions, attend)
        y, c = self._ffn(i, x, row_mask)
        return x + y, c

    def _layers(self, x, rows, lead, positions, attend, row_mask=None):
        """The layer loop both programs share, over ``layer_kinds``;
        ``attend`` is the program's own cache append + attention, handed
        to the stack's ``_attention``. Returns the hidden state and the
        routed layers' expert counts."""
        counts = []
        with stf.variable_scope("decoder"):
            for i, kind in enumerate(self.layer_kinds):
                with stf.variable_scope(f"layer_{i}"):
                    x, c = self._layer(kind, i, x, rows, lead, positions,
                                       attend, row_mask)
                    if c is not None:
                        counts.append(c)
        return x, counts

    # -- a hyper-connected residual (mHC), for a stack whose cfg asks for
    # it with ``hc_mult`` streams (ops/hyper_connection_ops.py) ----------
    def _streams(self, x):
        """``x (rows, d_model)`` -> the residual's ``(rows, n, d_model)``,
        the embedding in every stream."""
        return stf.tile(stf.expand_dims(x, 1), [1, self.cfg.hc_mult, 1])

    def _hyper(self, x, name, fn):
        """``X <- H_res X + H_post^T fn(H_pre X)`` around one sublayer,
        its coefficients under ``name``."""
        cfg = self.cfg
        n, width = cfg.hc_mult, cfg.hc_mult * cfg.d_model
        coef = 2 * n + n * n
        h, post, res = stf.nn.hyper_connection_pre(
            x, self._w(f"{name}/phi", [width, coef], width,
                       dtype=stf.float32),
            stf.get_variable(f"{name}/scale", [3], dtype=stf.float32,
                             initializer=stf.ones_initializer()),
            stf.get_variable(f"{name}/bias", [coef], dtype=stf.float32,
                             initializer=stf.zeros_initializer()),
            iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
            norm_eps=cfg.rms_norm_eps)
        return stf.nn.hyper_connection_post(x, fn(h), post, res)

    def _merged(self, x):
        """The streams summed, in float32, for the head."""
        return stf.cast(stf.reduce_sum(stf.cast(x, stf.float32), axis=1),
                        self.compute_dtype)

    def _logits(self, x, tied=False):
        """The final norm and the head: an untied ``lm_head``, or with
        ``tied`` the embedding's own rows."""
        cfg = self.cfg
        h = self._norm(x, "final_norm", cfg.d_model)
        if tied:
            logits = stf.matmul(h, self._embedding(), transpose_b=True)
        else:
            logits = stf.matmul(h, self._w(
                "lm_head", [cfg.d_model, cfg.vocab_size], cfg.d_model))
        return stf.cast(logits, stf.float32)


def build_causal_lm_program(cfg: TransformerConfig, *,
                            compute_dtype=stf.float32, int8=False,
                            scope="causal_lm", tp_axis=None, **kw):
    """:func:`build_paged_lm_program` over the post-LN stack
    ``causal_lm_logits`` trains (``_block_decode`` /
    ``_incremental_decode``, tied-embedding head, optional int8 head)."""
    return build_paged_lm_program(
        _PostLNStack(cfg, compute_dtype, scope, int8=int8, tp_axis=tp_axis),
        compute_dtype=compute_dtype, scope=scope, tp_axis=tp_axis, **kw)


def build_paged_lm_program(stack, *, page_len, pages_per_seq, num_pages,
                           max_live=None, decode_bucket_sizes=None,
                           prefill_bucket_sizes=None,
                           compute_dtype=stf.float32, sampling=None,
                           scope="causal_lm", cache_sharding=None,
                           tp_axis=None):
    """Build the paged-cache serving programs of a decoder-only block
    ``stack`` (:class:`_PostLNStack`; ``models/sparse_moe_lm.py`` and
    ``models/latent_moe_lm.py`` have :class:`PreNormStack`s): the caches, the bucket loops, the emit and copy-on-write
    are here once, the layer mathematics is the stack's.

    Emits, in the CURRENT default graph:

    - the stack's per-layer caches ``(num_pages + 1, page_len, *inner)``
      with ``paged=True`` (row ``num_pages`` is the scratch page bucket
      padding writes into) + ``alloc_op``;
    - one PREFILL program per prefill bucket pb: a page-aligned BLOCK
      of ``page_len`` prompt tokens through ``stack.prefill_block``,
      appended into each row's ``dst_pages`` physical page (feeds: tok
      (pb, page_len), base (pb,) absolute start, page_tables
      (pb, n_blocks), dst_pages (pb,); fetches: the append group — no
      logits: the engine feeds the last prompt token through the first
      DECODE step instead, so a partial final chunk just pads);
    - one DECODE program per decode bucket sb: one position through
      ``stack.decode_step`` (feeds: tok (sb,), pos (sb,) absolute,
      page_tables (sb, n_blocks), dst_pages (sb,), offsets (sb,), slots
      (sb,); fetches next_tok/logp (sb,), and whatever else the stack
      returns by name under ``extra``) — greedy, or seeded sampling when
      ``sampling`` is set;
    - ``last_tok``: the device's own record of each sequence's last
      token, ``(max_live + 1,)`` int32 addressed by SLOT (the last row is
      the scratch slot of padding rows; allocated under ``alloc_op``).
      A decode program takes a row's token from it where the fed token
      is negative — ``tok = where(tok >= 0, tok, last_tok[slot])`` — and
      writes the row's ``next_tok`` back into it. So the host can
      enqueue step k + 1 before it has fetched step k's tokens: it feeds
      -1 for a continuing sequence and a token only for a sequence's
      first step;
    - ``cow``: the copy-on-write program — ``KVCachePageCopy`` over
      EVERY paged cache of every layer (feeds dst (1,), src (1,)): a
      sequence diverging inside a shared page copies it before private
      appends.

    A stack whose ``layer_caches`` names a ``StatePool`` — state addressed
    by SLOT beside the pages, ``(max_live + 1, *inner)`` with the last row
    the scratch slot of padding rows (``stack.layer_caches`` is then
    called with ``state_slots=max_live + 1``) — gets two more feeds in
    prefill, and only such a stack does: ``slots (pb,)``, each row's
    sequence's slot, and ``lens (pb,)``, the real tokens of each row's
    chunk (``max_live`` defaults to the largest decode bucket). Rows of
    one prefill call are walked IN THE ORDER
    GIVEN by the layers that keep state (``ops/ssm_ops.py``). A state
    pool is allocated under ``alloc_op`` with the pages and is in no
    ``cow``. ``state_pools`` in the result says whether there are any.

    Page tables are host-side state (the prefix-cache trie owns them);
    the device only ever sees the resolved (page_tables, dst, offset)
    integers, so admission/eviction never retraces a program.
    """
    from ..serving.policy import _pow2_buckets
    from ..ops import kv_cache_ops as kvc

    if tp_axis and cache_sharding is None:
        cache_sharding = f"{tp_axis}{kvc.HEAD_SHARD_SUFFIX}"

    def _feed(t):
        """Annotate a placeholder replicated-on-mesh under TP (same
        contract as the seq2seq builder: fed numpy must commit onto the
        mesh's device set next to the head-sharded paged caches)."""
        if tp_axis:
            from simple_tensorflow_tpu import parallel

            parallel.shard_feed(t)
        return t

    page_len = int(page_len)
    pages_per_seq = int(pages_per_seq)
    num_pages = int(num_pages)
    max_seq_len = page_len * pages_per_seq
    if max_seq_len > stack.max_positions:
        raise ValueError(
            f"page_len*pages_per_seq={max_seq_len} exceeds "
            f"cfg.max_len={stack.max_positions} (position-encoding table)")
    total_pages = num_pages + 1          # + scratch page
    scratch_page = num_pages
    decode_buckets = sorted(set(int(x) for x in (
        decode_bucket_sizes or _pow2_buckets(8))))
    prefill_buckets = sorted(set(int(x) for x in (
        prefill_bucket_sizes or (1,))))

    if max_live is None:
        max_live = decode_buckets[-1]
    if getattr(stack, "keeps_state", False):
        caches = stack.layer_caches(kvc, total_pages, page_len,
                                    cache_sharding,
                                    state_slots=int(max_live) + 1)
    else:
        caches = stack.layer_caches(kvc, total_pages, page_len,
                                    cache_sharding)
    flat_caches = [c for group in caches for c in group]
    state_pools = any(not c.paged for c in flat_caches)
    # a (slots, 1) int32 cache read and written by slot at position 0:
    # the one value a sequence keeps there is its last token
    last_tok = kvc.kv_cache(f"{scope}_pg/last_tok", int(max_live) + 1, 1,
                            (), stf.int32)
    alloc_op = stf.group(*[c.alloc() for c in flat_caches + [last_tok]],
                         name="pg_alloc")

    if sampling is not None:
        sampling = dict(sampling)
        unknown = set(sampling) - {"temperature", "top_k", "top_p",
                                   "seed"}
        if unknown:
            raise ValueError(f"unknown sampling knobs: {sorted(unknown)}")

    def _emit(logits):
        if sampling is not None:
            from ..ops import sampling_ops

            return sampling_ops.sample_token(logits, **sampling)
        logp_all = stf.nn.log_softmax(logits, axis=-1)
        tok = stf.cast(stf.argmax(logits, -1, output_type=stf.int32),
                       stf.int32)
        logp = stf.reduce_sum(
            logp_all * stf.one_hot(tok, stack.vocab_size,
                                   dtype=stf.float32), axis=-1)
        return tok, logp

    # -- prefill: one page-aligned chunk ------------------------------------
    prefill = {}
    for pb in prefill_buckets:
        tok = _feed(stf.placeholder(stf.int32, [pb, page_len],
                                    f"lm_prefill{pb}_tok"))
        base = _feed(stf.placeholder(stf.int32, [pb],
                                     f"lm_prefill{pb}_base"))
        tables = _feed(stf.placeholder(stf.int32, [pb, pages_per_seq],
                                       f"lm_prefill{pb}_tables"))
        dst = _feed(stf.placeholder(stf.int32, [pb],
                                    f"lm_prefill{pb}_dst"))
        by_slot = {}
        if state_pools:
            by_slot = {
                "slots": _feed(stf.placeholder(stf.int32, [pb],
                                               f"lm_prefill{pb}_slots")),
                "lens": _feed(stf.placeholder(stf.int32, [pb],
                                              f"lm_prefill{pb}_lens"))}
        cache = _PagedCaches(caches, tables, dst, stf.fill([pb], 0),
                             base, **by_slot)
        h = stack.prefill_block(tok, base, cache)
        # fetch the hidden state to anchor the whole block (appends are
        # its data deps); pad rows of a partial final chunk write
        # garbage K/V past the real length — dead rows: attention masks
        # by committed length and the next append overwrites in place
        prefill[pb] = {"tok": tok, "base": base, "tables": tables,
                       "dst": dst, **by_slot,
                       "op": stf.group(h, name=f"lm_prefill{pb}")}

    # -- decode: one position -----------------------------------------------
    decode_progs = {}
    for sb in decode_buckets:
        tok = _feed(stf.placeholder(stf.int32, [sb], f"lm_decode{sb}_tok"))
        pos = _feed(stf.placeholder(stf.int32, [sb], f"lm_decode{sb}_pos"))
        tables = _feed(stf.placeholder(stf.int32, [sb, pages_per_seq],
                                       f"lm_decode{sb}_tables"))
        dst = _feed(stf.placeholder(stf.int32, [sb],
                                    f"lm_decode{sb}_dst"))
        off = _feed(stf.placeholder(stf.int32, [sb],
                                    f"lm_decode{sb}_off"))
        slots = _feed(stf.placeholder(stf.int32, [sb],
                                      f"lm_decode{sb}_slots"))
        by_slot = {"slots": slots} if state_pools else {}
        cache = _PagedCaches(caches, tables, dst, off, pos, **by_slot)
        held = stf.reshape(last_tok.gather(slots), [sb])
        logits, extra = stack.decode_step(
            stf.where(stf.greater_equal(tok, 0), tok, held), pos, cache)
        next_tok, logp = _emit(logits)
        wrote = last_tok.append(stf.reshape(next_tok, [sb, 1]), slots,
                                np.zeros((sb,), np.int32))
        with stf.control_dependencies([wrote.op]):
            next_tok = stf.identity(next_tok)
        decode_progs[sb] = {"tok": tok, "pos": pos, "tables": tables,
                            "dst": dst, "off": off, "slots": slots,
                            "next_tok": next_tok, "logp": logp,
                            "logits": logits, "extra": extra}

    # -- copy-on-write ------------------------------------------------------
    cow_dst = _feed(stf.placeholder(stf.int32, [1], "lm_cow_dst"))
    cow_src = _feed(stf.placeholder(stf.int32, [1], "lm_cow_src"))
    cow_op = stf.group(*[c.copy_pages(cow_dst, cow_src)
                         for c in flat_caches if c.paged], name="lm_cow")

    return {
        "alloc_op": alloc_op,
        "int8_init": getattr(stack, "int8_init", None),
        "prefill": prefill,
        "decode": decode_progs,
        "cow": {"dst": cow_dst, "src": cow_src, "op": cow_op},
        "decode_buckets": decode_buckets,
        "prefill_buckets": prefill_buckets,
        "scratch_page": scratch_page,
        "caches": caches,
        "last_tok": last_tok,
        "state_pools": state_pools,
        "cache_sharding": cache_sharding,
        "tp_axis": tp_axis,
    }


class _DecodeCall:
    """A decode step enqueued and not yet collected: the plan's fetches
    as futures, the live rows, their positions and the bucket."""

    __slots__ = ("out", "n", "positions", "bucket")

    def __init__(self, out, n, positions, bucket):
        self.out, self.n = out, n
        self.positions, self.bucket = positions, bucket


class CausalLMGenerativeModel:
    """Session-owning paged causal-LM decode programs for the serving
    engine's prefix-cache path.

    The engine (serving/generative.py) owns the page-table bookkeeping
    through :class:`~..serving.prefix_cache.PrefixCache`; this model
    exposes the device half: ``prefill_chunk`` (one page-aligned block
    per live row), ``decode_async`` / ``collect`` (one position, enqueued,
    then fetched; physical page/offset resolved from the page table HERE,
    host-side; ``decode`` is the two at once), ``copy_page`` (CoW),
    and the ``page_len / num_pages / pages_per_seq / scratch_page``
    geometry the pool is sized against.
    """

    def __init__(self, cfg: TransformerConfig, *, page_len=8,
                 pages_per_seq=4, num_pages=32, max_live=8,
                 decode_bucket_sizes=None, prefill_bucket_sizes=None,
                 compute_dtype=stf.float32, int8=False, sampling=None,
                 checkpoint=None, init_fresh=False, config=None,
                 scope="causal_lm", aot_warmup=True, seed=0,
                 mesh=None, tp=None, metrics_label=None):
        if checkpoint is None and not init_fresh:
            raise ValueError("pass checkpoint=... or init_fresh=True")
        self.cfg = cfg
        # labels the model's per-step samplers: give it the name the
        # model is served under
        self._metrics_label = metrics_label or scope
        self.page_len = int(page_len)
        self.pages_per_seq = int(pages_per_seq)
        self.num_pages = int(num_pages)
        self.max_seq_len = self.page_len * self.pages_per_seq
        # engine-facing decode geometry (slot == live sequence)
        self.num_slots = int(max_live)
        self.max_decode_len = self.max_seq_len
        self.src_len = 0                     # decoder-only: no encoder
        self.eos_id = cfg.eos_id
        self.pad_id = cfg.pad_id
        self.int8 = bool(int8)
        self.sampling = dict(sampling) if sampling else None
        self._compute_dtype = compute_dtype
        self._cache_bytes_total, self._cache_bytes_unsharded = \
            self._cache_bytes()
        self.tp_choice = None
        if tp == "auto":
            from ..analysis import autoshard as _autoshard

            budget = int(getattr(config, "device_memory_budget_bytes",
                                 0) or 0) or None
            self.tp_choice = _autoshard.choose_decode_tp(
                num_heads=cfg.num_heads,
                cache_bytes=self._cache_bytes_total,
                unsharded_bytes=self._cache_bytes_unsharded,
                collective_bytes_fn=lambda t: decode_tp_collective_bytes(
                    cfg, t, compute_dtype, cross=False),
                budget_bytes=budget, mesh=mesh)
            tp = self.tp_choice.degree
        self._mesh, self.tp_axis, self.tp_degree = resolve_decode_tp(
            mesh, tp, cfg.num_heads)
        self.graph = stf.Graph()
        with contextlib.ExitStack() as _scope_stack:
            _scope_stack.enter_context(self.graph.as_default())
            if self._mesh is not None:
                _scope_stack.enter_context(self._mesh)
            if seed is not None:
                stf.set_random_seed(seed)
            self.session = stf.Session(graph=self.graph, config=config)
            prog = self._build_program(
                page_len=page_len, pages_per_seq=pages_per_seq,
                num_pages=num_pages, max_live=self.num_slots,
                decode_bucket_sizes=(decode_bucket_sizes
                                     or tuple(sorted({1, max_live}))),
                prefill_bucket_sizes=prefill_bucket_sizes,
                compute_dtype=compute_dtype, sampling=sampling,
                scope=scope, tp_axis=self.tp_axis)
            self._prog = prog
            self.scratch_page = prog["scratch_page"]
            # state addressed by SLOT beside the pages (a recurrent
            # layer's): the engine then hands over each row's slot and
            # real length, shares no prefix and takes no draft
            self.state_outside_pages = prog["state_pools"]
            self.scratch_slot_row = self.num_slots
            if self.tp_axis:
                # commit the TP weight layout BEFORE restore/init so
                # the Session places (checkpoint-restored or fresh)
                # state sharded at first commit
                from simple_tensorflow_tpu import parallel

                parallel.match_partition_rules(
                    decode_tp_partition_rules(self.tp_axis), apply=True)
            if checkpoint is not None:
                saver = stf.train.Saver()
                saver.restore(self.session, checkpoint)
            else:
                self.session.run(stf.global_variables_initializer())
            init_fetches = [prog["alloc_op"]]
            if prog["int8_init"] is not None:
                init_fetches.append(prog["int8_init"])
            for f in init_fetches:
                self.session.run(f)
            self._decode_plans = {}
            for sb, p in prog["decode"].items():
                plan = self.session.plan(
                    {"next_tok": p["next_tok"], "logp": p["logp"],
                     **p["extra"]},
                    feeds=[p["tok"], p["pos"], p["tables"], p["dst"],
                           p["off"], p["slots"]])
                self._decode_plans[sb] = (plan, p)
                if aot_warmup:
                    plan.compile()
            self._prefill_plans = {}
            for pb, p in prog["prefill"].items():
                plan = self.session.plan(
                    {"done": p["op"]},
                    feeds=[p["tok"], p["base"], p["tables"], p["dst"]]
                    + [p[k] for k in ("slots", "lens") if k in p])
                self._prefill_plans[pb] = (plan, p)
                if aot_warmup:
                    plan.compile()
            cw = prog["cow"]
            self._cow_plan = (self.session.plan(
                {"done": cw["op"]}, feeds=[cw["dst"], cw["src"]]), cw)
            if aot_warmup:
                self._cow_plan[0].compile()
        self._decode_buckets = sorted(self._decode_plans)
        self._prefill_buckets = sorted(self._prefill_plans)
        if aot_warmup:
            # which buckets an admission runs follows how many rows its
            # prompts pack into a call: no bucket's first execution is
            # left to the traffic. Pad rows write the scratch page only
            none = np.zeros((0,), np.int32)
            for pb in self._prefill_buckets:
                self._prefill_call(pb, none.reshape(0, self.page_len), none,
                                   self._scratch_tables(0), none)

    # -- what a subclass with another block stack overrides ------------------
    def _cache_bytes(self):
        """(total, unsharded) bytes of the paged cache set: the same as
        ``generative_cache_bytes`` with slots=num_pages,
        decode_len=page_len, no cross caches."""
        return generative_cache_bytes(self.cfg, 0, self.num_pages,
                                      self.page_len, self._compute_dtype,
                                      cross=False)

    def _build_program(self, **kw):
        return build_causal_lm_program(self.cfg, int8=self.int8, **kw)

    def _after_decode(self, out, n, positions):
        """Once a decode step, with what it fetched beside the tokens
        (``extra``). Here: the share of the step's page-table entries
        that hold a live page, host-known — what paged decode attention
        reads of the tables it is handed (the rest it skips)."""
        live = -(-(np.asarray(positions[:n], np.int64) + 1)
                 // self.page_len)
        _live_page_share.get_cell(self._metrics_label).add(
            float(live.sum()) / (n * self.pages_per_seq))

    @property
    def decode_buckets(self):
        return list(self._decode_buckets)

    @property
    def prefill_buckets(self):
        return list(self._prefill_buckets)

    def _bucket(self, buckets, n):
        for b in buckets:
            if b >= n:
                return b
        raise ValueError(f"{n} rows exceed the largest bucket "
                         f"{buckets[-1]}")

    def _run(self, plan, feed, as_futures=None):
        """Execute under the model's mesh scope (thread-local; the
        engine's scheduler thread is not inside the construction-time
        ``with mesh:``)."""
        if self._mesh is None:
            return plan.execute(feed, as_futures=as_futures)
        with self._mesh:
            return plan.execute(feed, as_futures=as_futures)

    def tp_info(self):
        """Decode-TP facts for telemetry (/stf/serving/tp_*)."""
        t = max(int(self.tp_degree or 1), 1)
        sharded = self._cache_bytes_total - self._cache_bytes_unsharded
        per_device = self._cache_bytes_unsharded + sharded // t
        return {
            "tp_degree": t,
            "tp_axis": self.tp_axis,
            "cache_bytes_replicated": int(self._cache_bytes_total),
            "cache_bytes_per_device": int(per_device),
            "per_token_collective_bytes": int(decode_tp_collective_bytes(
                self.cfg, t, self._compute_dtype, cross=False)),
        }

    def _scratch_tables(self, n):
        return np.full((n, self.pages_per_seq), self.scratch_page,
                       np.int32)

    def _prefill_call(self, pb, tok_chunks, bases, page_tables, dst_pages,
                      slots=(), lens=()):
        """One execution of prefill bucket ``pb``: the rows given, then
        pad rows that read and write the scratch page (and the scratch
        slot) alone."""
        plan, p = self._prefill_plans[pb]
        with monitoring.traceme("model/prefill_feeds"):
            n = len(dst_pages)
            tok = np.full((pb, self.page_len), self.pad_id, np.int32)
            base = np.zeros((pb,), np.int32)
            tbl = self._scratch_tables(pb)
            dst = np.full((pb,), self.scratch_page, np.int32)
            tok[:n], base[:n], tbl[:n], dst[:n] = \
                tok_chunks, bases, page_tables, dst_pages
            feed = {p["tok"]: tok, p["base"]: base, p["tables"]: tbl,
                    p["dst"]: dst}
            if self.state_outside_pages:
                slot = np.full((pb,), self.scratch_slot_row, np.int32)
                real = np.zeros((pb,), np.int32)
                slot[:n], real[:n] = slots, lens
                feed.update({p["slots"]: slot, p["lens"]: real})
        self._run(plan, feed)

    def prefill_chunk(self, tok_chunks, bases, page_tables, dst_pages,
                      slots=None, lens=None):
        """Run n page-aligned prompt chunks, one a row: ``tok_chunks
        (n, page_len)`` (pad-padded past the real tail), ``bases (n,)``
        absolute chunk start (multiple of page_len), ``page_tables
        (n, pages_per_seq)``, ``dst_pages (n,)`` the physical page each
        row's chunk fills. Rows are independent but for the pool: a
        layer appends every row of a call, then each row attends through
        its table over the ``bases[i]`` positions before it, so a row
        may read pages that rows BEFORE it in the order given fill —
        chunks of one prompt in order of ``base`` are n rows of one
        call. The rows are cut, in that order, into as many calls of the
        largest prefill bucket as they fill and the remainder in the
        smallest bucket that holds it. Returns the calls made.

        A model with state outside its pages (``state_outside_pages``)
        also takes ``slots (n,)``, each row's sequence's slot, and ``lens
        (n,)``, the real tokens of each row's chunk: its recurrent layers
        walk a call's rows in the order given — a slot's rows in order of
        ``base`` — start a row of ``base`` 0 from zero state, and stop a
        row's recurrence at its last real token."""
        with monitoring.traceme("model/prefill_feeds"):
            tok_chunks = np.asarray(tok_chunks, np.int32).reshape(
                -1, self.page_len)
            bases = np.asarray(bases, np.int32)
            page_tables = np.asarray(page_tables, np.int32).reshape(
                -1, self.pages_per_seq)
            dst_pages = np.asarray(dst_pages, np.int32)
            n = len(dst_pages)
            by_slot = ()
            if self.state_outside_pages:
                if slots is None or lens is None:
                    raise ValueError(
                        f"{type(self).__name__} keeps state outside its "
                        "pages: prefill_chunk needs each row's slot and "
                        "real length")
                by_slot = (np.asarray(slots, np.int32),
                           np.asarray(lens, np.int32))
                real = int(by_slot[1].sum())
                _prefill_real_tokens.get_cell(
                    self._metrics_label).increase_by(real)
                _prefill_pad_tokens.get_cell(
                    self._metrics_label).increase_by(
                        n * self.page_len - real)
                if n:
                    _prefill_pad_share.get_cell(self._metrics_label).add(
                        1.0 - real / (n * self.page_len))
            rows_a_call = _prefill_call_rows.get_cell(self._metrics_label)
        calls = done = 0
        while done < n:
            take = min(n - done, self._prefill_buckets[-1])
            sl = slice(done, done + take)
            self._prefill_call(self._bucket(self._prefill_buckets, take),
                               tok_chunks[sl], bases[sl], page_tables[sl],
                               dst_pages[sl], *(a[sl] for a in by_slot))
            rows_a_call.add(take)
            done += take
            calls += 1
        return calls

    def decode_async(self, tokens, positions, page_tables, slots=None):
        """Enqueue one decode position for n live sequences and return
        at once with a handle for :meth:`collect`. The physical write
        target is resolved host-side from each row's page table: ``dst =
        page_tables[i, pos // page_len]``, ``off = pos % page_len``.
        ``slots (n,)`` are the sequences' slots: a row whose token is
        negative takes the token that the step before left for its slot
        on the device (``last_tok`` of :func:`build_paged_lm_program`),
        and every row writes its own there. Without ``slots`` every row
        uses the scratch slot, so each token must be given; a model with
        state outside its pages always needs them."""
        with monitoring.traceme("model/decode_feeds"):
            tokens = np.asarray(tokens, np.int32)
            positions = np.asarray(positions, np.int32)
            page_tables = np.asarray(page_tables, np.int32).reshape(
                -1, self.pages_per_seq)
            n = len(tokens)
            if slots is None and self.state_outside_pages:
                raise ValueError(
                    f"{type(self).__name__} keeps state outside its "
                    "pages: decode needs each sequence's slot")
            sb = self._bucket(self._decode_buckets, n)
            plan, p = self._decode_plans[sb]
            tok = np.full((sb,), self.pad_id, np.int32)
            pos = np.zeros((sb,), np.int32)
            tbl = self._scratch_tables(sb)
            slot = np.full((sb,), self.scratch_slot_row, np.int32)
            tok[:n], pos[:n], tbl[:n] = tokens, positions, page_tables
            if slots is not None:
                slot[:n] = slots
            dst = tbl[np.arange(sb), pos // self.page_len]
            off = pos % self.page_len
            feed = {p["tok"]: tok, p["pos"]: pos, p["tables"]: tbl,
                    p["dst"]: dst, p["off"]: off.astype(np.int32),
                    p["slots"]: slot}
        return _DecodeCall(self._run(plan, feed, as_futures=True), n,
                           positions, sb)

    def collect(self, call):
        """Wait for a step :meth:`decode_async` enqueued and fetch what it
        computed: ``(next_tok (n,), logp (n,), bucket)``. A device error
        of the step raises here."""
        out = materialize(call.out)
        with monitoring.traceme("model/after_decode"):
            self._after_decode(out, call.n, call.positions)
            return (out["next_tok"][:call.n], out["logp"][:call.n],
                    call.bucket)

    def decode(self, tokens, positions, page_tables, slots=None):
        """One decode position for n live sequences, enqueued and
        collected (:meth:`decode_async`, :meth:`collect`). Returns
        (next_tok (n,), logp (n,), bucket)."""
        return self.collect(self.decode_async(tokens, positions,
                                              page_tables, slots))

    def copy_page(self, dst, src):
        """Copy-on-write: duplicate physical page ``src`` into ``dst``
        across every layer cache (one plan execution)."""
        plan, cw = self._cow_plan
        self._run(plan, {cw["dst"]: np.asarray([dst], np.int32),
                         cw["src"]: np.asarray([src], np.int32)})

    def close(self):
        self.session.close()

    def statusz_info(self):
        info = {"decode_buckets": self._decode_buckets,
                "prefill_buckets": self._prefill_buckets,
                "page_len": self.page_len, "num_pages": self.num_pages,
                "pages_per_seq": self.pages_per_seq,
                "num_slots": self.num_slots, "int8": self.int8,
                "sampling": self.sampling}
        for key, sampler in (("decode_live_page_share", _live_page_share),
                             ("prefill_rows_per_call", _prefill_call_rows)):
            cell = sampler.cells().get((self._metrics_label,))
            v = cell.value() if cell is not None else None
            if v and v["count"]:
                info[key] = v["sum"] / v["count"]
        if self.tp_degree > 1:
            info["tp"] = self.tp_info()
        return info
