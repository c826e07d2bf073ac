"""Transformer-big WMT en-de seq2seq with beam search (BASELINE config 5).

(ref: the reference targets "Transformer-big WMT en-de (seq2seq, staged
across TPU slice sub-meshes)".)

TPU-first choices:
- Every attention (encoder self, cross, causal decoder self) runs the
  Pallas flash-attention kernel; padding masks ride the kernel's additive
  key-bias input and attention dropout is generated in-kernel. All shapes
  static (fixed src/tgt lengths) for MXU tiling.
- bf16 activations, f32 parameters, fused Pallas LayerNorm, label-smoothed
  xent in f32.
- Beam search re-scores the full prefix each step — O(L^2) FLOPs but every
  iteration is the same static XLA program (no growing shapes, no host
  sync), which on TPU beats an incrementally-cached decoder that would
  retrace per length. Written entirely with stf graph ops lowering to one
  lax.while_loop.
- Pipeline-parallel staging lives in stf.parallel.pipeline ("staged across
  TPU slice sub-meshes"); data/tensor parallel via stf.parallel.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math

import numpy as np

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.models import common


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 1024
    num_heads: int = 16
    d_ff: int = 4096
    num_layers: int = 6
    dropout: float = 0.1
    label_smoothing: float = 0.1
    max_len: int = 256
    layer_norm_eps: float = 1e-6
    pad_id: int = 0
    eos_id: int = 1

    @staticmethod
    def big():
        return TransformerConfig()

    @staticmethod
    def base():
        return TransformerConfig(d_model=512, num_heads=8, d_ff=2048)

    @staticmethod
    def tiny():
        return TransformerConfig(vocab_size=64, d_model=32, num_heads=2,
                                 d_ff=64, num_layers=2, dropout=0.0,
                                 max_len=32)


def _init(cfg):
    return stf.variance_scaling_initializer(1.0, "fan_avg", "uniform")


def _ln(x, cfg, name):
    return common.layer_norm(x, name, eps=cfg.layer_norm_eps)


def _dense(x, units, cfg, name, activation=None):
    return common.dense(x, units, _init(cfg), name, activation=activation)


def _tp_gather(x, tp_axis):
    """All-gather a tp-sharded activation back to replicated.

    The ONE collective shape of the bit-exact decode-TP layout: heads
    (and the logits' vocab columns) are computed column-parallel — each
    device owns a full contraction for its slice, so every element is
    arithmetically identical to the single-device value — and this
    replicated constraint concatenates the slices (an XLA all-gather;
    no partial-sum all-reduce anywhere, so token streams stay
    bit-exact). The sharding-analysis rule prices the same all-gather,
    which is what keeps predicted vs harvested collective bytes in
    agreement. The input is first PINNED to its column-sharded layout
    (last dim on ``tp_axis``): without the pin the SPMD partitioner is
    free to replicate an operand upstream instead — for the tied
    logits head it would all-gather the whole vocab-sharded embedding
    table (d_model*vocab bytes) rather than the (n, vocab) logits row,
    turning the ONE cheap per-token collective into a weight-sized
    one. No-op when ``tp_axis`` is None (single-device build) or no
    mesh is active at lowering time."""
    if not tp_axis:
        return x
    from simple_tensorflow_tpu import parallel

    rank = x.shape.rank
    x = parallel.with_sharding_constraint(
        x, *([None] * (rank - 1) + [tp_axis]))
    return parallel.with_sharding_constraint(x, *([None] * rank))


def decode_tp_partition_rules(tp_axis="tp"):
    """Partition rules for the decode-tensor-parallel weight layout
    (apply via ``stf.parallel.match_partition_rules(..., apply=True)``
    after building the generative program, before restore/init).

    Decoder Q/K/V projections go column-parallel — output columns split
    over ``tp_axis``, matching the head-sharded KV cache layout
    (``"<axis>:heads"``) — and the tied softmax table vocab-shards so
    the logits matmul (and its int8 QuantMatMul twin) is
    column-parallel over vocab. Everything else (encoder, out/FFN/LN
    weights) is explicitly P(): replicated ON the mesh, so every
    decode-path array lives on the same device set. Encoder weights
    stay replicated on purpose — prefill numerics are untouched, and
    only the decode inner loop pays resharding."""
    from simple_tensorflow_tpu.parallel import P

    return [
        (r"decoder/.*/(self_attn|cross_attn)/(q|k|v)/kernel$",
         P(None, tp_axis)),
        (r"decoder/.*/(self_attn|cross_attn)/(q|k|v)/bias$", P(tp_axis)),
        (r"shared_embedding$", P(tp_axis, None)),
        (r"_int8_decode/emb_q$", P(None, tp_axis)),
        (r"_int8_decode/emb_scale$", P(tp_axis)),
        (r".*", P()),
    ]


def sinusoidal_position_encoding(max_len, d_model):
    """Classic sin/cos table as a numpy constant (host-computed once)."""
    pos = np.arange(max_len)[:, None].astype(np.float64)
    dim = np.arange(d_model // 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, 2.0 * dim / d_model)
    enc = np.zeros((max_len, d_model), np.float32)
    enc[:, 0::2] = np.sin(angle)
    enc[:, 1::2] = np.cos(angle)
    return enc


def _residual(sub_out, h, cfg, training):
    """Sublayer tail ``h + dropout(sub_out)`` through the fused
    dropout+bias+residual op (ops/fused_ops.py; the registry routes the
    Pallas kernel vs the composed-XLA chain — same counter-based mask
    either way). rate 0 (eval / dropout-free configs) builds a plain
    add, keeping those graphs identical to the pre-fusion form."""
    rate = cfg.dropout if training else 0.0
    return stf.nn.fused_bias_dropout_residual(sub_out, h, rate=rate)


def _attention(q_in, kv_in, bias, cfg, training, compute_dtype, name,
               causal=False):
    """q_in (B,Sq,D) attends over kv_in (B,Sk,D). bias additive or None.

    Always the Pallas flash-attention kernel: padding bias rides the
    kernel's additive key-bias input, causal masking and attention-prob
    dropout happen in-kernel (counter-based mask replayed in the vjp).
    The output-projection dropout moved into the fused
    dropout+residual tail (_residual) applied at the block level.
    """
    b = int(q_in.shape[0])
    sq, sk = int(q_in.shape[1]), int(kv_in.shape[1])
    d, heads = cfg.d_model, cfg.num_heads
    hd = d // heads
    with stf.variable_scope(name):
        q = _dense(q_in, d, cfg, "q")
        k = _dense(kv_in, d, cfg, "k")
        v = _dense(kv_in, d, cfg, "v")
        q = common.split_heads(q, b, sq, heads, hd)
        k = common.split_heads(k, b, sk, heads, hd)
        v = common.split_heads(v, b, sk, heads, hd)
        key_bias = stf.reshape(bias, [b, sk]) if bias is not None else None
        ctx = stf.nn.fused_attention(
            q, k, v, bias=key_bias, causal=causal,
            dropout_rate=cfg.dropout if training else 0.0)
        out = _dense(common.merge_heads(ctx, b, sq, d), d, cfg, "out")
    return out


def _ffn(x, cfg, training, name):
    with stf.variable_scope(name):
        h = _dense(x, cfg.d_ff, cfg, "in", activation=stf.nn.relu)
        if training and cfg.dropout > 0:
            h = stf.nn.dropout(h, keep_prob=1.0 - cfg.dropout)
        return _dense(h, cfg.d_model, cfg, "out")


def _embed(ids, cfg, compute_dtype, training):
    """Shared embedding table, scaled, plus sinusoidal positions."""
    emb = stf.get_variable(
        "shared_embedding", [cfg.vocab_size, cfg.d_model],
        initializer=stf.random_normal_initializer(
            stddev=cfg.d_model ** -0.5))
    s = int(ids.shape[1])
    # mixed-precision lookup: [B,S,D] activations move in compute dtype,
    # gradient scatter-add still accumulates into the table in f32
    h = stf.nn.embedding_lookup(emb, ids, compute_dtype=compute_dtype) \
        * stf.cast(stf.constant(cfg.d_model ** 0.5), compute_dtype)
    pos = sinusoidal_position_encoding(cfg.max_len, cfg.d_model)[:s]
    h = h + stf.cast(stf.constant(pos[None, :, :]), compute_dtype)
    if training and cfg.dropout > 0:
        h = stf.nn.dropout(h, keep_prob=1.0 - cfg.dropout)
    return h, emb


def _pad_bias(ids, cfg):
    """(B,S) ids -> additive bias (B,1,1,S): -1e9 on pad positions."""
    b, s = int(ids.shape[0]), int(ids.shape[1])
    is_pad = stf.cast(stf.equal(ids, cfg.pad_id), stf.float32)
    return stf.reshape(is_pad, [b, 1, 1, s]) * -1e9


def encode(src_ids, cfg, training=True, compute_dtype=stf.bfloat16,
           scope="transformer", recompute=False):
    with stf.variable_scope(scope, reuse=stf.AUTO_REUSE):
        h, _ = _embed(src_ids, cfg, compute_dtype, training)
        bias = _pad_bias(src_ids, cfg)
        with stf.variable_scope("encoder"):
            def enc_layer(hh, i):
                with stf.variable_scope(f"layer_{i}"):
                    a = _attention(hh, hh, bias, cfg, training,
                                   compute_dtype, "self_attn")
                    hh = _ln(_residual(a, hh, cfg, training), cfg, "ln1")
                    f = _ffn(hh, cfg, training, "ffn")
                    return _ln(hh + f, cfg, "ln2")

            for i in range(cfg.num_layers):
                h = common.maybe_recompute(enc_layer, h, i, recompute, "enc")
    return h, bias


def decode(tgt_ids, enc_out, enc_bias, cfg, training=True,
           compute_dtype=stf.bfloat16, scope="transformer",
           recompute=False):
    """Returns logits (B, St, vocab); causal self-attention over tgt_ids."""
    with stf.variable_scope(scope, reuse=stf.AUTO_REUSE):
        h, emb = _embed(tgt_ids, cfg, compute_dtype, training)
        with stf.variable_scope("decoder"):
            def dec_layer(hh, i):
                with stf.variable_scope(f"layer_{i}"):
                    a = _attention(hh, hh, None, cfg, training,
                                   compute_dtype, "self_attn", causal=True)
                    hh = _ln(_residual(a, hh, cfg, training), cfg, "ln1")
                    c = _attention(hh, enc_out, enc_bias, cfg, training,
                                   compute_dtype, "cross_attn")
                    hh = _ln(_residual(c, hh, cfg, training), cfg, "ln2")
                    f = _ffn(hh, cfg, training, "ffn")
                    return _ln(hh + f, cfg, "ln3")

            for i in range(cfg.num_layers):
                h = common.maybe_recompute(dec_layer, h, i, recompute, "dec")
        # tied softmax weights, computed in compute dtype: the
        # [B*S, vocab] logits are the largest tensor in the model, and the
        # fused xent kernel does its softmax math in f32 blockwise anyway
        b, s = int(tgt_ids.shape[0]), int(tgt_ids.shape[1])
        flat = stf.reshape(h, [b * s, cfg.d_model])
        logits = stf.matmul(flat, stf.cast(emb, h.dtype.base_dtype),
                            transpose_b=True)
        return stf.reshape(logits, [b, s, cfg.vocab_size])


def smoothed_xent(logits, labels, weights, cfg):
    """Label-smoothed cross entropy, weight-masked mean (f32 loss math).

    The smoothing is fused into the streamed softmax-xent kernel — the
    composed form materialized log_softmax AND a dense one-hot at
    [B*S, vocab], three vocab-sized f32 tensors the kernel never builds."""
    vocab = cfg.vocab_size
    conf = 1.0 - cfg.label_smoothing
    low = cfg.label_smoothing / (vocab - 1)
    per_tok = stf.nn.fused_softmax_cross_entropy(
        logits, labels, label_smoothing=cfg.label_smoothing)
    # subtract the entropy of the smoothed target => 0 loss at perfection
    norm = -(conf * math.log(conf) +
             (vocab - 1) * low * math.log(low + 1e-20))
    per_tok = per_tok - norm
    w = stf.cast(weights, stf.float32)
    return stf.reduce_sum(per_tok * w) / (stf.reduce_sum(w) + 1e-9)


def transformer_train_model(batch_size=64, src_len=64, tgt_len=64,
                            cfg: TransformerConfig | None = None,
                            learning_rate=1.0, warmup_steps=4000,
                            compute_dtype=stf.bfloat16, data_parallel=False,
                            recompute=False):
    """Training graph: src/tgt -> label-smoothed loss -> Adam + noam decay.
    recompute="auto" resolves against the attached chip's HBM via the
    static cost model (framework/cost_model.py resolve_recompute)."""
    cfg = cfg or TransformerConfig.big()
    from ..framework import cost_model as _cm

    # encoder layers see src_len, decoder layers tgt_len (cross-attn
    # keys add a little on top; the heuristic ignores it); per-chip
    # under a dp mesh
    _shards = _cm.mesh_shard_factor(["dp"] if data_parallel else [])
    _act = (_cm.transformer_activation_bytes(
                batch_size, src_len, cfg.d_model, cfg.num_layers,
                dtype_bytes=compute_dtype.size)
            + _cm.transformer_activation_bytes(
                batch_size, tgt_len, cfg.d_model, cfg.num_layers,
                dtype_bytes=compute_dtype.size))
    _flops = (_cm.transformer_forward_flops(
                  batch_size, src_len, cfg.d_model, cfg.num_layers,
                  d_ff=cfg.d_ff)
              + _cm.transformer_forward_flops(
                  batch_size, tgt_len, cfg.d_model, cfg.num_layers,
                  d_ff=cfg.d_ff))
    recompute = _cm.resolve_recompute(recompute, _act / _shards,
                                      forward_flops=_flops / _shards)
    src = stf.placeholder(stf.int32, [batch_size, src_len], "src_ids")
    tgt_in = stf.placeholder(stf.int32, [batch_size, tgt_len], "tgt_in")
    tgt_out = stf.placeholder(stf.int32, [batch_size, tgt_len], "tgt_out")
    if data_parallel:
        from simple_tensorflow_tpu import parallel
        mesh = parallel.current_mesh()
        if mesh is not None and "dp" in mesh.axis_names:
            for t in (src, tgt_in, tgt_out):
                parallel.shard_feed(t, "dp")

    enc_out, enc_bias = encode(src, cfg, training=True,
                               compute_dtype=compute_dtype,
                               recompute=recompute)
    logits = decode(tgt_in, enc_out, enc_bias, cfg, training=True,
                    compute_dtype=compute_dtype, recompute=recompute)
    weights = stf.cast(stf.not_equal(tgt_out, cfg.pad_id), stf.float32)
    loss = smoothed_xent(logits, tgt_out, weights, cfg)

    gs = stf.train.get_or_create_global_step()
    # noam schedule: d^-0.5 * min(step^-0.5, step*warmup^-1.5)
    step = stf.cast(gs, stf.float32) + 1.0
    lr = (learning_rate * cfg.d_model ** -0.5 *
          stf.minimum(stf.pow(step, -0.5), step * warmup_steps ** -1.5))
    opt = stf.train.AdamOptimizer(lr, beta1=0.9, beta2=0.997, epsilon=1e-9)
    train_op = opt.minimize(loss, global_step=gs)
    acc = stf.reduce_sum(stf.cast(stf.equal(
        stf.cast(stf.argmax(logits, -1, output_type=stf.int32), stf.int32),
        tgt_out), stf.float32) * weights) / (stf.reduce_sum(weights) + 1e-9)
    return {"src_ids": src, "tgt_in": tgt_in, "tgt_out": tgt_out,
            "loss": loss, "train_op": train_op, "accuracy": acc,
            "learning_rate": lr, "global_step": gs}


# ---------------------------------------------------------------------------
# Incremental (KV-cached) decode
# ---------------------------------------------------------------------------

class _GatheredCaches:
    """What ``_incremental_decode`` / ``_block_decode`` ask a cache
    accessor for — the attention of one layer over its cache with the
    new rows appended — answered by a cache that is a dense row a
    sequence: gather it, run the gathered-view kernel. (The paged
    accessor, models/causal_lm.py, answers with attention read straight
    off the pool.)"""

    def attend(self, layer, q, k_new, v_new):
        k_all, v_all, lengths = self.append_and_gather(layer, k_new, v_new)
        return stf.nn.decode_attention(q, k_all, v_all, lengths)

    def attend_block(self, layer, q, k_new, v_new):
        k_all, v_all, base = self.append_and_gather_block(layer, k_new,
                                                          v_new)
        return stf.nn.decode_attention(q, k_all, v_all, base,
                                       causal_offset=True)


class _BeamCaches(_GatheredCaches):
    """Loop-carried functional caches for the cached beam search: one
    (k, v) pair per decoder layer, each (B, L, H, hd), updated in-place
    functionally via a one-hot position mask (static shapes — the whole
    search stays ONE XLA program)."""

    def __init__(self, flat_arrays, i, b, max_len):
        self._arrays = list(flat_arrays)
        self._i = i
        self._b = b
        self._L = max_len
        self.updated = list(flat_arrays)

    def append_and_gather(self, layer, k_new, v_new):
        mask = stf.cast(stf.reshape(
            stf.one_hot(self._i, self._L, dtype=stf.float32),
            [1, self._L, 1, 1]), k_new.dtype.base_dtype)
        k_all = self._arrays[2 * layer] * (1.0 - mask) + k_new * mask
        v_all = self._arrays[2 * layer + 1] * (1.0 - mask) + v_new * mask
        self.updated[2 * layer] = k_all
        self.updated[2 * layer + 1] = v_all
        lengths = stf.fill([self._b], self._i + 1)
        return k_all, v_all, lengths


class _SlotCaches(_GatheredCaches):
    """Variable-backed paged caches for the serving decode step: each
    layer's k/v live device-resident in the VariableStore
    (ops/kv_cache_ops.py); appends scatter at (slot, position) and the
    gather rides a control dependency so the RAW is graph-ordered.

    ``verify_plan=True`` (the speculative VERIFY program) stamps every
    append with the ``_verify_plan``/``_refcount_guarded`` attr pair —
    the lint/serving-decode-cache contract that verify-plan cache
    writes commit only through the engine's accepted-prefix refcount
    bookkeeping."""

    def __init__(self, caches, slots, positions, verify_plan=False):
        self._caches = caches          # [(KVCache k, KVCache v)] per layer
        self._slots = slots
        self._pos = positions
        self._verify = bool(verify_plan)

    def append_and_gather(self, layer, k_new, v_new):
        kc, vc = self._caches[layer]
        k_all = kc.append_and_gather(k_new, self._slots, self._pos,
                                     verify_plan=self._verify,
                                     refcount_guarded=self._verify)
        v_all = vc.append_and_gather(v_new, self._slots, self._pos,
                                     verify_plan=self._verify,
                                     refcount_guarded=self._verify)
        return k_all, v_all, self._pos + 1

    def append_and_gather_block(self, layer, k_new, v_new):
        """Block variant: ``k_new/v_new (B, Kq, H, hd)`` append at
        positions ``pos..pos+Kq-1``; returns the gathered caches plus
        the BASE length (committed prefix before the block) —
        DecodeAttention's ``causal_offset=True`` contract."""
        kc, vc = self._caches[layer]
        k_all = kc.append_and_gather(k_new, self._slots, self._pos,
                                     verify_plan=self._verify,
                                     refcount_guarded=self._verify)
        v_all = vc.append_and_gather(v_new, self._slots, self._pos,
                                     verify_plan=self._verify,
                                     refcount_guarded=self._verify)
        return k_all, v_all, self._pos


def _decode_cross_kv(enc_out, cfg, compute_dtype, scope):
    """Per-layer cross-attention K/V projections of the encoder output,
    computed ONCE per sequence (the naive re-forward path recomputes
    them every emitted token). Returns [(ck, cv)] each
    (B, S_src, H, hd) — the DecodeAttention cache layout."""
    b, s = int(enc_out.shape[0]), int(enc_out.shape[1])
    d, heads = cfg.d_model, cfg.num_heads
    hd = d // heads
    out = []
    with stf.variable_scope(scope, reuse=stf.AUTO_REUSE):
        with stf.variable_scope("decoder"):
            for i in range(cfg.num_layers):
                with stf.variable_scope(f"layer_{i}"):
                    with stf.variable_scope("cross_attn"):
                        ck = stf.reshape(_dense(enc_out, d, cfg, "k"),
                                         [b, s, heads, hd])
                        cv = stf.reshape(_dense(enc_out, d, cfg, "v"),
                                         [b, s, heads, hd])
                out.append((ck, cv))
    return out


def _incremental_decode(tok, pos, caches, cross_kv, cross_bias, cross_len,
                        cfg, compute_dtype, scope, tp_axis=None):
    """ONE decoder position for B sequences against cached state.

    tok: (B,) int32 input tokens; pos: scalar or (B,) int32 position(s);
    caches: a :class:`_BeamCaches` / :class:`_SlotCaches` accessor (or
    the paged one of models/causal_lm.py): ``caches.attend(layer, q,
    k_new, v_new)`` appends the new rows and returns the attention;
    cross_kv: [(ck, cv)] per layer (B, S_src, H, hd); cross_bias:
    (B, S_src) additive f32; cross_len: (B,) int32. Returns
    (h (B, d_model) in compute dtype, emb) — the caller owns the logits
    matmul (f32/bf16 tied softmax, or the int8 QuantMatMul route).

    Token-for-token equivalent to selecting position ``pos`` of the
    full re-forward :func:`decode` at eval time: every sublayer here is
    position-independent (LN, FFN, residual) or reads exactly the
    positions the causal mask admits (self-attention over the cache,
    cross-attention over the full source).

    ``cross_kv=None`` builds the decoder-only (causal LM) step: the
    cross-attention sublayer — and its ``ln2`` — is skipped entirely,
    matching the sublayer/LN naming of
    :func:`~.causal_lm.causal_lm_logits`.

    ``tp_axis``: decode tensor parallelism — Q/K/V run column-parallel
    (heads split over the axis, see :func:`decode_tp_partition_rules`),
    attention runs per-shard against the head-sharded cache with zero
    collectives, and the context all-gathers back to replicated
    (:func:`_tp_gather`) right before each output projection.
    """
    b = int(tok.shape[0])
    d, heads = cfg.d_model, cfg.num_heads
    hd = d // heads
    with stf.variable_scope(scope, reuse=stf.AUTO_REUSE):
        emb = stf.get_variable(
            "shared_embedding", [cfg.vocab_size, cfg.d_model],
            initializer=stf.random_normal_initializer(
                stddev=cfg.d_model ** -0.5))
        h = stf.nn.embedding_lookup(emb, tok, compute_dtype=compute_dtype) \
            * stf.cast(stf.constant(cfg.d_model ** 0.5), compute_dtype)
        pos_table = stf.constant(
            sinusoidal_position_encoding(cfg.max_len, cfg.d_model))
        h = h + stf.cast(stf.gather(pos_table, pos), compute_dtype)
        with stf.variable_scope("decoder"):
            for i in range(cfg.num_layers):
                with stf.variable_scope(f"layer_{i}"):
                    with stf.variable_scope("self_attn"):
                        q = stf.reshape(_dense(h, d, cfg, "q"),
                                        [b, heads, hd])
                        k_new = stf.reshape(_dense(h, d, cfg, "k"),
                                            [b, 1, heads, hd])
                        v_new = stf.reshape(_dense(h, d, cfg, "v"),
                                            [b, 1, heads, hd])
                        a = caches.attend(i, q, k_new, v_new)
                        a = _tp_gather(stf.reshape(a, [b, d]), tp_axis)
                        a = _dense(a, d, cfg, "out")
                    h = _ln(_residual(a, h, cfg, False), cfg, "ln1")
                    if cross_kv is not None:
                        with stf.variable_scope("cross_attn"):
                            qc = stf.reshape(_dense(h, d, cfg, "q"),
                                             [b, heads, hd])
                            ck, cv = cross_kv[i]
                            c = stf.nn.decode_attention(
                                qc, ck, cv, cross_len, bias=cross_bias)
                            c = _tp_gather(stf.reshape(c, [b, d]),
                                           tp_axis)
                            c = _dense(c, d, cfg, "out")
                        h = _ln(_residual(c, h, cfg, False), cfg, "ln2")
                    f = _ffn(h, cfg, False, "ffn")
                    h = _ln(h + f, cfg, "ln3")
    return h, emb


def _block_decode(tok_block, pos, caches, cross_kv, cross_bias, cross_len,
                  cfg, compute_dtype, scope, tp_axis=None):
    """A BLOCK of Kq consecutive decoder positions for B sequences.

    tok_block: (B, Kq) int32 input tokens at positions
    ``pos[b]..pos[b]+Kq-1``; pos: (B,) int32 committed prefix per
    sequence BEFORE the block; caches: an accessor with
    ``attend_block`` (:class:`_SlotCaches`, or the paged
    variant in models/causal_lm.py); cross args as in
    :func:`_incremental_decode` (``cross_kv=None`` for decoder-only).
    Returns (h (B, Kq, d_model), emb).

    This is the speculative VERIFY shape — the target model re-scores
    the draft's K proposals in ONE pass, self-attention running the
    query-block DecodeAttention kernel with ``causal_offset=True``
    (query j sees the committed prefix plus block positions <= j) — and
    also the causal-LM page-block prefill shape. Per-position it is
    arithmetic-identical to Kq chained :func:`_incremental_decode`
    steps: every sublayer is position-local, and the block attention
    admits exactly the positions the chained steps would have seen.
    """
    b, kq = int(tok_block.shape[0]), int(tok_block.shape[1])
    d, heads = cfg.d_model, cfg.num_heads
    hd = d // heads
    with stf.variable_scope(scope, reuse=stf.AUTO_REUSE):
        emb = stf.get_variable(
            "shared_embedding", [cfg.vocab_size, cfg.d_model],
            initializer=stf.random_normal_initializer(
                stddev=cfg.d_model ** -0.5))
        h = stf.nn.embedding_lookup(emb, tok_block,
                                    compute_dtype=compute_dtype) \
            * stf.cast(stf.constant(cfg.d_model ** 0.5), compute_dtype)
        pos_table = stf.constant(
            sinusoidal_position_encoding(cfg.max_len, cfg.d_model))
        pos_idx = stf.reshape(pos, [b, 1]) + stf.constant(
            np.arange(kq, dtype=np.int32).reshape(1, kq))
        h = h + stf.cast(stf.gather(pos_table, pos_idx), compute_dtype)
        with stf.variable_scope("decoder"):
            for i in range(cfg.num_layers):
                with stf.variable_scope(f"layer_{i}"):
                    with stf.variable_scope("self_attn"):
                        q = stf.reshape(_dense(h, d, cfg, "q"),
                                        [b, kq, heads, hd])
                        k_new = stf.reshape(_dense(h, d, cfg, "k"),
                                            [b, kq, heads, hd])
                        v_new = stf.reshape(_dense(h, d, cfg, "v"),
                                            [b, kq, heads, hd])
                        a = caches.attend_block(i, q, k_new, v_new)
                        a = _tp_gather(stf.reshape(a, [b, kq, d]),
                                       tp_axis)
                        a = _dense(a, d, cfg, "out")
                    h = _ln(_residual(a, h, cfg, False), cfg, "ln1")
                    if cross_kv is not None:
                        with stf.variable_scope("cross_attn"):
                            qc = stf.reshape(_dense(h, d, cfg, "q"),
                                             [b, kq, heads, hd])
                            ck, cv = cross_kv[i]
                            c = stf.nn.decode_attention(
                                qc, ck, cv, cross_len, bias=cross_bias)
                            c = _tp_gather(stf.reshape(c, [b, kq, d]),
                                           tp_axis)
                            c = _dense(c, d, cfg, "out")
                        h = _ln(_residual(c, h, cfg, False), cfg, "ln2")
                    f = _ffn(h, cfg, False, "ffn")
                    h = _ln(h + f, cfg, "ln3")
    return h, emb


def beam_search_decode(src, cfg: TransformerConfig | None = None,
                       beam_size=4, decode_len=None, alpha=0.6,
                       compute_dtype=stf.bfloat16, scope="transformer",
                       use_cache=False):
    """Beam search over the decoder; returns (ids (B,beam,L), scores (B,beam)).

    Fixed decode_len iterations of one static XLA program via stf.while_loop;
    Finished beams (EOS emitted) are extended only by EOS at zero cost, so
    scores freeze.

    use_cache=False re-scores the full prefix each step (O(L^2) FLOPs,
    see the module docstring); use_cache=True carries per-layer KV
    caches through the loop and decodes ONE position per step through
    the DecodeAttention kernel (O(L) FLOPs) — token-for-token the same
    search (int-exact ids; scores to float round-off).
    """
    cfg = cfg or TransformerConfig.big()
    b = int(src.shape[0])
    L = decode_len or cfg.max_len
    if L > cfg.max_len:
        # the position-encoding table has cfg.max_len rows; a longer
        # decode would silently clamp the gather (wrong tokens, no
        # error) on the cached path
        raise ValueError(
            f"decode_len={L} exceeds cfg.max_len={cfg.max_len}")
    k = beam_size
    vocab = cfg.vocab_size
    neg_inf = -1e9
    heads = cfg.num_heads
    hd = cfg.d_model // heads

    enc_out, enc_bias = encode(src, cfg, training=False,
                               compute_dtype=compute_dtype, scope=scope)
    # tile encoder outputs over beams: (B,S,D) -> (B*k,S,D)
    s_src, d = int(enc_out.shape[1]), int(enc_out.shape[2])
    enc_tiled = stf.reshape(
        stf.tile(stf.expand_dims(enc_out, 1), [1, k, 1, 1]),
        [b * k, s_src, d])
    bias_tiled = stf.reshape(
        stf.tile(stf.expand_dims(enc_bias, 1), [1, k, 1, 1, 1]),
        [b * k, 1, 1, s_src])

    # state: i, seq (B,k,L) started with EOS column 0, logp (B,k)
    seq0 = stf.concat([
        stf.fill([b, k, 1], cfg.eos_id),
        stf.fill([b, k, L - 1], cfg.pad_id)], axis=2)
    # only beam 0 alive initially so the k first expansions differ
    logp0 = stf.constant(
        np.tile(np.array([[0.0] + [neg_inf] * (k - 1)], np.float32), (b, 1)))
    i0 = stf.constant(0)

    eos_row = stf.constant(
        np.array([0.0 if t == cfg.eos_id else neg_inf
                  for t in range(vocab)], np.float32).reshape(1, 1, vocab))
    offs = stf.reshape(stf.constant(
        np.arange(b, dtype=np.int32) * k), [b, 1])

    def select(i, seq, logp, step_logits):
        """Beam expansion shared by both paths: score position ``i``'s
        logits, pick the top-k continuations, write the token at column
        i+1. Returns (new_seq, new_logp, parent (B*k,) row indices)."""
        logprobs = stf.nn.log_softmax(step_logits, axis=-1)
        logprobs = stf.reshape(logprobs, [b, k, vocab])

        # finished beams (already emitted EOS after t=0) may only extend
        # with EOS at zero cost
        emitted = stf.reduce_sum(stf.cast(stf.equal(
            stf.slice(seq, [0, 0, 1], [b, k, L - 1]), cfg.eos_id),
            stf.float32), axis=2)
        finished = stf.greater(emitted, 0.0)  # (B,k)
        fin_f = stf.reshape(stf.cast(finished, stf.float32), [b, k, 1])
        logprobs = logprobs * (1.0 - fin_f) + eos_row * fin_f

        total = stf.reshape(logp, [b, k, 1]) + logprobs  # (B,k,vocab)
        flat_total = stf.reshape(total, [b, k * vocab])
        new_logp, flat_idx = stf.nn.top_k(flat_total, k=k)  # (B,k)
        beam_idx = stf.cast(flat_idx // vocab, stf.int32)  # (B,k)
        tok = stf.cast(flat_idx % vocab, stf.int32)  # (B,k)

        # gather parent rows: batch offsets into (B*k, L)
        parent = stf.reshape(beam_idx + offs, [-1])
        new_seq = stf.gather(stf.reshape(seq, [b * k, L]), parent)
        # write token at column i+1 via one_hot mask (static shapes)
        col = stf.one_hot(i + 1, L, dtype=stf.int32)  # (L,)
        new_seq = (new_seq * (1 - stf.reshape(col, [1, L])) +
                   stf.reshape(tok, [-1, 1]) * stf.reshape(col, [1, L]))
        return stf.reshape(new_seq, [b, k, L]), new_logp, parent

    def cond(i, seq, logp, *caches):
        return stf.less(i, L - 1)

    def body_naive(i, seq, logp):
        flat = stf.reshape(seq, [b * k, L])
        # decode() emits logits in compute dtype; beam-score math is f32
        logits = stf.cast(
            decode(flat, enc_tiled, bias_tiled, cfg, training=False,
                   compute_dtype=compute_dtype, scope=scope), stf.float32)
        # logits at position i predict token i+1: one_hot-select (static L)
        sel = stf.one_hot(i, L, dtype=stf.float32)  # (L,)
        step_logits = stf.reduce_sum(
            logits * stf.reshape(sel, [1, L, 1]), axis=1)  # (B*k, vocab)
        new_seq, new_logp, _ = select(i, seq, logp, step_logits)
        return i + 1, new_seq, new_logp

    if use_cache:
        cross_kv = _decode_cross_kv(enc_tiled, cfg, compute_dtype, scope)
        cross_bias = stf.reshape(bias_tiled, [b * k, s_src])
        cross_len = stf.fill([b * k], s_src)
        caches0 = []
        for _ in range(cfg.num_layers):
            caches0.append(stf.zeros([b * k, L, heads, hd],
                                     dtype=compute_dtype))
            caches0.append(stf.zeros([b * k, L, heads, hd],
                                     dtype=compute_dtype))

        def body_cached(i, seq, logp, *flat_caches):
            # current input token = column i of every beam row
            coli = stf.one_hot(i, L, dtype=stf.int32)
            tok = stf.reduce_sum(seq * stf.reshape(coli, [1, 1, L]),
                                 axis=2)  # (B,k)
            flat_tok = stf.reshape(tok, [b * k])
            cache = _BeamCaches(flat_caches, i, b * k, L)
            h, emb = _incremental_decode(
                flat_tok, i, cache, cross_kv, cross_bias, cross_len,
                cfg, compute_dtype, scope)
            logits = stf.matmul(h, stf.cast(emb, h.dtype.base_dtype),
                                transpose_b=True)
            step_logits = stf.cast(logits, stf.float32)
            new_seq, new_logp, parent = select(i, seq, logp, step_logits)
            # beams reorder -> their caches reorder with them
            new_caches = [stf.gather(c, parent) for c in cache.updated]
            return (i + 1, new_seq, new_logp, *new_caches)

        out = stf.while_loop(cond, body_cached,
                             [i0, seq0, logp0] + caches0)
        _, seq, logp = out[0], out[1], out[2]
    else:
        _, seq, logp = stf.while_loop(cond, body_naive, [i0, seq0, logp0])
    # GNMT length penalty, then re-sort: penalties vary with beam length,
    # so raw-logp order need not equal penalized order
    lengths = stf.reduce_sum(stf.cast(stf.logical_and(
        stf.not_equal(seq, cfg.pad_id), stf.not_equal(seq, cfg.eos_id)),
        stf.float32), axis=2) + 1.0
    penalty = stf.pow((5.0 + lengths) / 6.0, alpha)
    scores = logp / penalty
    scores, order = stf.nn.top_k(scores, k=k)  # (B,k) descending
    offs = stf.reshape(stf.constant(np.arange(b, dtype=np.int32) * k),
                       [b, 1])
    flat_order = stf.reshape(stf.cast(order, stf.int32) + offs, [-1])
    seq = stf.reshape(stf.gather(stf.reshape(seq, [b * k, L]), flat_order),
                      [b, k, L])
    return seq, scores


# ---------------------------------------------------------------------------
# Serving-side generative program (stf.serving.generative)
# ---------------------------------------------------------------------------

def build_int8_logits_weights(emb, cfg, scope="transformer"):
    """Column-wise int8 quantization of the tied softmax weights for the
    decode path: ``emb (vocab, d)`` → ``wq (d, vocab) int8`` +
    ``scale (vocab,) f32`` variables, quantized ON DEVICE by the
    returned init op (run it AFTER restoring the model weights). The
    decode logits matmul then routes through the QuantMatMul kernel
    registry entry — int8 runs the MXU at 2x the bf16 rate and halves
    the vocab-sized weight read per emitted token."""
    d, vocab = cfg.d_model, cfg.vocab_size
    with stf.variable_scope(f"{scope}_int8_decode",
                            reuse=stf.AUTO_REUSE):
        wq = stf.get_variable("emb_q", [d, vocab], dtype=stf.int8,
                              initializer=stf.zeros_initializer(),
                              trainable=False,
                              collections=["stf_decode_int8"])
        scale = stf.get_variable("emb_scale", [vocab], dtype=stf.float32,
                                 initializer=stf.ones_initializer(),
                                 trainable=False,
                                 collections=["stf_decode_int8"])
        w = stf.transpose(stf.cast(emb, stf.float32), [1, 0])  # (d, vocab)
        s = stf.maximum(stf.reduce_max(stf.abs(w), axis=0), 1e-8) / 127.0
        q = stf.cast(stf.round(w / stf.reshape(s, [1, vocab])), stf.int8)
        init = stf.group(stf.assign(wq, q), stf.assign(scale, s),
                         name="int8_decode_init")
    return wq, scale, init


def build_generative_program(cfg: TransformerConfig, src_len, *,
                             num_slots, max_decode_len,
                             decode_bucket_sizes=None,
                             prefill_bucket_sizes=(1,),
                             compute_dtype=stf.float32, int8=False,
                             scope="transformer", cache_sharding=None,
                             sampling=None, speculative_k=None,
                             draft_steps=None, tp_axis=None):
    """Build the paged-cache decode graphs for token-level serving.

    Emits, in the CURRENT default graph:

    - per-layer self-attention K/V caches + per-layer cross-attention
      K/V caches + the source padding-bias cache, all device-resident
      ``KVCache`` pages with ``num_slots + 1`` rows (the extra row is
      the SCRATCH slot bucket padding writes into, so a padded decode
      row can never corrupt a live sequence's cache);
    - ``alloc_op``: zero-fills every cache (engine start);
    - one PREFILL program per ``prefill_bucket_sizes`` entry: encoder
      forward + cross-K/V projection, scattered into the slots' cache
      rows (feeds: src (pb, src_len), slots (pb,));
    - one DECODE program per ``decode_bucket_sizes`` entry: ONE
      position for sb sequences — embed, per-layer cached self-attn
      (KVCacheAppend at (slot, pos) then DecodeAttention), cached
      cross-attn, tied-softmax logits (QuantMatMul when ``int8``),
      greedy argmax (feeds: tok (sb,), pos (sb,), slots (sb,);
      fetches: next_tok (sb,), logp (sb,));
    - with ``sampling={"temperature": .., "top_k": .., "top_p": ..}``
      the decode (and verify) programs SAMPLE instead of argmax —
      seeded Gumbel-max on the per-step RNG stream
      (ops/sampling_ops.py), so the plan reports ``uses_rng`` and
      ``set_random_seed`` reproduces token streams;
    - with ``speculative_k=K``, one VERIFY program per decode bucket:
      re-score a (sb, K) token block in ONE pass through the
      query-block DecodeAttention kernel (feeds tok (sb, K), pos (sb,),
      slots (sb,); fetches next_tok/logp (sb, K)) — the target side of
      speculative decoding; its cache appends carry the
      ``_verify_plan``/``_refcount_guarded`` attr pair;
    - with ``draft_steps=Kd``, one DRAFT program per decode bucket: Kd
      chained greedy decode steps unrolled into ONE executable (feeds
      tok (sb,), pos (sb,), slots (sb,); fetches props (sb, Kd)) — the
      draft side: one dispatch proposes Kd tokens.

    With ``tp_axis`` set (decode tensor parallelism) the caches default
    to the head-sharded ``"<axis>:heads"`` layout, the decode/verify/
    draft bodies thread the axis into :func:`_incremental_decode` /
    :func:`_block_decode` (context all-gather before out-projections),
    the logits head all-gathers its column-parallel output (the ONE
    per-token vocab-sized collective), and every feed placeholder is
    annotated replicated-on-mesh so host feeds commit onto the same
    device set as the sharded state.

    Returns a dict of graph handles (see :class:`TransformerGenerativeModel`
    for the session-owning wrapper the serving engine drives).
    """
    from ..serving.policy import _pow2_buckets

    if max_decode_len > cfg.max_len:
        raise ValueError(
            f"max_decode_len={max_decode_len} exceeds "
            f"cfg.max_len={cfg.max_len} (the position-encoding table); "
            "raise cfg.max_len or shorten the cache")
    heads = cfg.num_heads
    hd = cfg.d_model // heads
    total_slots = int(num_slots) + 1      # + scratch row
    scratch = int(num_slots)
    decode_buckets = sorted(set(int(x) for x in (
        decode_bucket_sizes or _pow2_buckets(int(num_slots)))))
    prefill_buckets = sorted(set(int(x) for x in prefill_bucket_sizes))
    from ..ops import kv_cache_ops as kvc

    if tp_axis and cache_sharding is None:
        cache_sharding = f"{tp_axis}{kvc.HEAD_SHARD_SUFFIX}"

    def _feed(t):
        """Annotate a placeholder replicated-on-mesh under TP: the fed
        numpy commits onto the mesh's device set (a single-device feed
        array next to 8-device sharded caches would be an XLA
        incompatible-devices error)."""
        if tp_axis:
            from simple_tensorflow_tpu import parallel

            parallel.shard_feed(t)
        return t

    self_caches = []
    cross_caches = []
    for i in range(cfg.num_layers):
        self_caches.append((
            kvc.kv_cache(f"{scope}_kv/l{i}_k", total_slots, max_decode_len,
                         (heads, hd), compute_dtype,
                         sharding=cache_sharding),
            kvc.kv_cache(f"{scope}_kv/l{i}_v", total_slots, max_decode_len,
                         (heads, hd), compute_dtype,
                         sharding=cache_sharding)))
        cross_caches.append((
            kvc.kv_cache(f"{scope}_kv/l{i}_ck", total_slots, src_len,
                         (heads, hd), compute_dtype,
                         sharding=cache_sharding),
            kvc.kv_cache(f"{scope}_kv/l{i}_cv", total_slots, src_len,
                         (heads, hd), compute_dtype,
                         sharding=cache_sharding)))
    bias_cache = kvc.kv_cache(f"{scope}_kv/src_bias", total_slots, src_len,
                              (), stf.float32, sharding=cache_sharding)

    all_caches = [c for pair in self_caches + cross_caches for c in pair]
    all_caches.append(bias_cache)
    alloc_op = stf.group(*[c.alloc() for c in all_caches],
                         name="kv_alloc")

    # -- prefill programs ----------------------------------------------------
    prefill = {}
    for pb in prefill_buckets:
        src = _feed(stf.placeholder(stf.int32, [pb, src_len],
                                    f"prefill{pb}_src"))
        slots = _feed(stf.placeholder(stf.int32, [pb],
                                      f"prefill{pb}_slots"))
        zeros = stf.fill([pb], 0)
        enc_out, enc_bias = encode(src, cfg, training=False,
                                   compute_dtype=compute_dtype,
                                   scope=scope)
        cross_kv = _decode_cross_kv(enc_out, cfg, compute_dtype, scope)
        appends = []
        for i, (ckc, cvc) in enumerate(cross_caches):
            ck, cv = cross_kv[i]
            appends.append(ckc.append(ck, slots, zeros))
            appends.append(cvc.append(cv, slots, zeros))
        appends.append(bias_cache.append(
            stf.reshape(enc_bias, [pb, src_len]), slots, zeros))
        prefill[pb] = {
            "src": src, "slots": slots,
            "op": stf.group(*appends, name=f"prefill{pb}"),
        }

    # -- decode programs -----------------------------------------------------
    if sampling is not None:
        sampling = dict(sampling)
        unknown = set(sampling) - {"temperature", "top_k", "top_p",
                                   "seed"}
        if unknown:
            raise ValueError(f"unknown sampling knobs: {sorted(unknown)}")
    state = {"int8_init": None, "wq": None, "w_scale": None}

    def _logits_head(h_flat, emb):
        """(n, d_model) -> f32 logits (n, vocab): tied softmax, or the
        int8 QuantMatMul route (weights quantized once, shared by
        decode AND verify programs). Under TP the weights are
        vocab-sharded (column-parallel logits, every column a full
        contraction) and the output all-gathers back to replicated —
        the ONE vocab-sized collective per emitted token; emission
        (argmax/sampling) then runs on bit-exact replicated logits."""
        if int8:
            if state["int8_init"] is None:
                state["wq"], state["w_scale"], state["int8_init"] = \
                    build_int8_logits_weights(emb, cfg, scope=scope)
            logits = stf.nn.quantized_matmul(h_flat, state["wq"],
                                             state["w_scale"])
        else:
            logits = stf.matmul(h_flat,
                                stf.cast(emb, h_flat.dtype.base_dtype),
                                transpose_b=True)
        return _tp_gather(stf.cast(logits, stf.float32), tp_axis)

    def _emit(logits):
        """f32 logits (n, vocab) -> (tok (n,), logp (n,)): greedy
        argmax, or the seeded sampling chain when ``sampling`` is on."""
        if sampling is not None:
            from ..ops import sampling_ops

            return sampling_ops.sample_token(logits, **sampling)
        logp_all = stf.nn.log_softmax(logits, axis=-1)
        tok = stf.cast(stf.argmax(logits, -1, output_type=stf.int32),
                       stf.int32)
        logp = stf.reduce_sum(
            logp_all * stf.one_hot(tok, cfg.vocab_size,
                                   dtype=stf.float32), axis=-1)
        return tok, logp

    def _cross_gather(slots):
        cross_bias = bias_cache.gather(slots)            # (sb, src_len)
        cross_kv = [(ckc.gather(slots), cvc.gather(slots))
                    for ckc, cvc in cross_caches]
        return cross_kv, cross_bias

    decode_progs = {}
    for sb in decode_buckets:
        tok = _feed(stf.placeholder(stf.int32, [sb], f"decode{sb}_tok"))
        pos = _feed(stf.placeholder(stf.int32, [sb], f"decode{sb}_pos"))
        slots = _feed(stf.placeholder(stf.int32, [sb],
                                      f"decode{sb}_slots"))
        cross_len = stf.fill([sb], src_len)
        cross_kv, cross_bias = _cross_gather(slots)
        cache = _SlotCaches(self_caches, slots, pos)
        h, emb = _incremental_decode(
            tok, pos, cache, cross_kv, cross_bias, cross_len, cfg,
            compute_dtype, scope, tp_axis=tp_axis)
        next_tok, logp = _emit(_logits_head(h, emb))
        decode_progs[sb] = {"tok": tok, "pos": pos, "slots": slots,
                            "next_tok": next_tok, "logp": logp}

    # -- speculative VERIFY programs (target side) ---------------------------
    verify_progs = {}
    if speculative_k:
        kv_width = int(speculative_k)
        for sb in decode_buckets:
            tok = _feed(stf.placeholder(stf.int32, [sb, kv_width],
                                        f"verify{sb}_tok"))
            pos = _feed(stf.placeholder(stf.int32, [sb],
                                        f"verify{sb}_pos"))
            slots = _feed(stf.placeholder(stf.int32, [sb],
                                          f"verify{sb}_slots"))
            cross_len = stf.fill([sb], src_len)
            cross_kv, cross_bias = _cross_gather(slots)
            cache = _SlotCaches(self_caches, slots, pos,
                                verify_plan=True)
            h, emb = _block_decode(
                tok, pos, cache, cross_kv, cross_bias, cross_len, cfg,
                compute_dtype, scope, tp_axis=tp_axis)
            flat = stf.reshape(h, [sb * kv_width, cfg.d_model])
            t_flat, lp_flat = _emit(_logits_head(flat, emb))
            verify_progs[sb] = {
                "tok": tok, "pos": pos, "slots": slots,
                "next_tok": stf.reshape(t_flat, [sb, kv_width]),
                "logp": stf.reshape(lp_flat, [sb, kv_width])}

    # -- DRAFT programs: Kd greedy steps in one executable -------------------
    draft_progs = {}
    if draft_steps:
        kd = int(draft_steps)
        for sb in decode_buckets:
            tok = _feed(stf.placeholder(stf.int32, [sb],
                                        f"draft{sb}_tok"))
            pos = _feed(stf.placeholder(stf.int32, [sb],
                                        f"draft{sb}_pos"))
            slots = _feed(stf.placeholder(stf.int32, [sb],
                                          f"draft{sb}_slots"))
            cross_len = stf.fill([sb], src_len)
            cross_kv, cross_bias = _cross_gather(slots)
            cur, props = tok, []
            for j in range(kd):
                # step j+1's appends hang off step j's gathers through
                # the argmax data path (cur), so the per-step cache
                # RAW/WAR hazards are graph-ordered without explicit
                # control edges. Proposals are ALWAYS greedy — the
                # verify side decides acceptance (greedy: token match;
                # sampling: match against the target's sample).
                cache = _SlotCaches(self_caches, slots, pos + j)
                h, emb = _incremental_decode(
                    cur, pos + j, cache, cross_kv, cross_bias,
                    cross_len, cfg, compute_dtype, scope,
                    tp_axis=tp_axis)
                logits = _logits_head(h, emb)
                cur = stf.cast(
                    stf.argmax(logits, -1, output_type=stf.int32),
                    stf.int32)
                props.append(stf.reshape(cur, [sb, 1]))
            draft_progs[sb] = {"tok": tok, "pos": pos, "slots": slots,
                               "props": stf.concat(props, axis=1)}

    return {
        "alloc_op": alloc_op,
        "int8_init": state["int8_init"],
        "prefill": prefill,
        "decode": decode_progs,
        "verify": verify_progs,
        "draft": draft_progs,
        "decode_buckets": decode_buckets,
        "prefill_buckets": prefill_buckets,
        "scratch_slot": scratch,
        "self_caches": self_caches,
        "cross_caches": cross_caches,
        "bias_cache": bias_cache,
        "cache_sharding": cache_sharding,
        "tp_axis": tp_axis,
    }


def generative_cache_bytes(cfg, src_len, num_slots, max_decode_len,
                           compute_dtype, cross=True):
    """(total_bytes, unsharded_bytes) of the generative cache set.

    ``total`` is the replicated footprint; ``unsharded`` is the part a
    head-dim TP layout can NOT divide (the rank-2 src-bias cache). Per
    device under tp=t: ``unsharded + (total - unsharded) / t`` — the
    number the HBM ledger, the tp_* metrics, and autoshard's
    per-device budget all reason about."""
    heads = cfg.num_heads
    hd = cfg.d_model // heads
    ts = int(num_slots) + 1
    per = compute_dtype.size
    total = 2 * cfg.num_layers * ts * max_decode_len * heads * hd * per
    unsharded = 0
    if cross:
        total += 2 * cfg.num_layers * ts * src_len * heads * hd * per
        unsharded = ts * src_len * 4          # src-bias cache, rank 2
    return total + unsharded, unsharded


def decode_tp_collective_bytes(cfg, tp_degree, compute_dtype,
                               cross=True):
    """Predicted per-token (per-sequence) collective bytes of the TP
    decode step, priced like the sharding rules price them: the
    vocab-sharded embedding lookup's all-reduce, one context
    all-gather per attention sublayer (2 per layer with cross
    attention, 1 without), and the single vocab-sized logits
    all-gather (f32). Zero at tp=1."""
    if not tp_degree or int(tp_degree) <= 1:
        return 0
    csize = compute_dtype.size
    d = cfg.d_model
    n_gathers = (2 if cross else 1) * cfg.num_layers
    return (d * csize                      # embedding-lookup all-reduce
            + n_gathers * d * csize        # context all-gathers
            + cfg.vocab_size * 4)          # logits all-gather


def resolve_decode_tp(mesh, tp, num_heads):
    """Normalize the (mesh, tp) model kwargs to
    ``(mesh | None, tp_axis | None, tp_degree)``.

    - both None / tp in (0, 1): single-device decode (no mesh);
    - ``tp=N`` with no mesh: builds ``Mesh({"tp": N})`` over the first
      N local devices;
    - a mesh with a ``tp`` axis: the degree is that axis' size (a
      ``tp=N`` kwarg must agree).

    The head count must divide by the degree — head-dim sharding is
    whole heads per device (attention never splits inside a head)."""
    degree = None if tp is None else int(tp)
    if mesh is None and (degree is None or degree <= 1):
        return None, None, 1
    from simple_tensorflow_tpu import parallel

    if mesh is None:
        import jax

        avail = len(jax.devices())
        if degree > avail:
            raise ValueError(
                f"tp={degree} exceeds the {avail} available devices")
        mesh = parallel.Mesh({"tp": degree})
    else:
        axis = mesh.shape.get("tp", 1)
        if axis <= 1:
            raise ValueError(
                f"mesh {mesh.shape} has no tp axis (>1); decode tensor "
                "parallelism shards over axis 'tp'")
        if degree is None:
            degree = int(axis)
        elif degree != int(axis):
            raise ValueError(
                f"tp={degree} disagrees with the mesh's tp axis size "
                f"{axis}")
    if degree <= 1:
        return None, None, 1
    if num_heads % degree:
        raise ValueError(
            f"num_heads={num_heads} not divisible by tp={degree}: "
            "head-dim sharding places whole heads per device")
    return mesh, "tp", degree


class TransformerGenerativeModel:
    """Session-owning transformer decode program for the serving engine.

    Implements the :class:`~...serving.generative.GenerativeEngine`
    model interface: ``prefill(src_rows, slots)``, ``decode(tokens,
    positions, slots) -> (next_tok, logp)``, ``close()``, plus the
    ``eos_id / pad_id / num_slots / max_decode_len / src_len`` attrs
    the engine schedules against. Owns its own Graph + Session (the
    per-model isolation contract of ModelServer servables); weights
    restore from ``checkpoint`` or initialize fresh
    (``init_fresh=True`` — tests/benches). All decode/prefill bucket
    programs are planned at construction and optionally AOT-compiled.
    """

    def __init__(self, cfg: TransformerConfig, src_len, *, num_slots=8,
                 max_decode_len=32, decode_bucket_sizes=None,
                 prefill_bucket_sizes=(1,), compute_dtype=stf.float32,
                 int8=False, checkpoint=None, init_fresh=False,
                 config=None, scope="transformer", aot_warmup=True,
                 seed=0, sampling=None, speculative_k=None,
                 draft_steps=None, mesh=None, tp=None):
        if checkpoint is None and not init_fresh:
            raise ValueError("pass checkpoint=... or init_fresh=True")
        self.cfg = cfg
        self.src_len = int(src_len)
        self.num_slots = int(num_slots)
        self.max_decode_len = int(max_decode_len)
        self.eos_id = cfg.eos_id
        self.pad_id = cfg.pad_id
        self.int8 = bool(int8)
        self.sampling = dict(sampling) if sampling else None
        self.spec_k = int(speculative_k) if speculative_k else 0
        self.draft_steps = int(draft_steps) if draft_steps else 0
        self._compute_dtype = compute_dtype
        self._cache_bytes_total, self._cache_bytes_unsharded = \
            generative_cache_bytes(cfg, self.src_len, self.num_slots,
                                   self.max_decode_len, compute_dtype)
        self.tp_choice = None
        if tp == "auto":
            # serving/decode autoshard purpose: pick the degree from
            # the roofline objective + per-device cache budget instead
            # of a hand flag
            from ..analysis import autoshard as _autoshard

            budget = int(getattr(config, "device_memory_budget_bytes",
                                 0) or 0) or None
            self.tp_choice = _autoshard.choose_decode_tp(
                num_heads=cfg.num_heads,
                cache_bytes=self._cache_bytes_total,
                unsharded_bytes=self._cache_bytes_unsharded,
                collective_bytes_fn=lambda t: decode_tp_collective_bytes(
                    cfg, t, compute_dtype),
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
            prog = build_generative_program(
                cfg, src_len, num_slots=num_slots,
                max_decode_len=max_decode_len,
                decode_bucket_sizes=decode_bucket_sizes,
                prefill_bucket_sizes=prefill_bucket_sizes,
                compute_dtype=compute_dtype, int8=int8, scope=scope,
                sampling=sampling, speculative_k=speculative_k,
                draft_steps=draft_steps, tp_axis=self.tp_axis)
            self._prog = prog
            self._scratch = prog["scratch_slot"]
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
                # quantize AFTER the weights are live
                init_fetches.append(prog["int8_init"])
            for f in init_fetches:
                self.session.run(f)
            self._decode_plans = {}
            for sb, p in prog["decode"].items():
                plan = self.session.plan(
                    {"next_tok": p["next_tok"], "logp": p["logp"]},
                    feeds=[p["tok"], p["pos"], p["slots"]])
                self._decode_plans[sb] = (plan, p)
                if aot_warmup:
                    plan.compile()
            self._verify_plans = {}
            for sb, p in prog.get("verify", {}).items():
                plan = self.session.plan(
                    {"next_tok": p["next_tok"], "logp": p["logp"]},
                    feeds=[p["tok"], p["pos"], p["slots"]])
                self._verify_plans[sb] = (plan, p)
                if aot_warmup:
                    plan.compile()
            self._draft_plans = {}
            for sb, p in prog.get("draft", {}).items():
                plan = self.session.plan(
                    {"props": p["props"]},
                    feeds=[p["tok"], p["pos"], p["slots"]])
                self._draft_plans[sb] = (plan, p)
                if aot_warmup:
                    plan.compile()
            self._prefill_plans = {}
            for pb, p in prog["prefill"].items():
                plan = self.session.plan({"done": p["op"]},
                                         feeds=[p["src"], p["slots"]])
                self._prefill_plans[pb] = (plan, p)
                if aot_warmup:
                    plan.compile()
        self._decode_buckets = sorted(self._decode_plans)
        self._prefill_buckets = sorted(self._prefill_plans)

    # the engine drives bucketing from its DecodePolicy: these expose
    # what this model actually compiled plans for (validated at
    # GenerativeEngine construction), and the scratch row bucket
    # padding may safely write into
    @property
    def decode_buckets(self):
        return list(self._decode_buckets)

    @property
    def prefill_buckets(self):
        return list(self._prefill_buckets)

    @property
    def scratch_slot(self):
        return self._scratch

    # -- engine interface -----------------------------------------------------
    def _bucket(self, buckets, n):
        for b in buckets:
            if b >= n:
                return b
        raise ValueError(f"{n} rows exceed the largest bucket "
                         f"{buckets[-1]}")

    def _run(self, plan, feed):
        """Execute under the model's mesh scope: the mesh stack is
        thread-local and the engine's scheduler thread is not inside
        the construction-time ``with mesh:``, so every execute re-enters
        it (feed staging + any retrace must see the mesh)."""
        if self._mesh is None:
            return plan.execute(feed)
        with self._mesh:
            return plan.execute(feed)

    def tp_info(self):
        """Decode-TP facts for telemetry (/stf/serving/tp_*): degree,
        per-device cache bytes under the committed layout, and the
        predicted per-token collective bytes (0 at tp=1)."""
        t = max(int(self.tp_degree or 1), 1)
        sharded = self._cache_bytes_total - self._cache_bytes_unsharded
        per_device = self._cache_bytes_unsharded + sharded // t
        return {
            "tp_degree": t,
            "tp_axis": self.tp_axis,
            "cache_bytes_replicated": int(self._cache_bytes_total),
            "cache_bytes_per_device": int(per_device),
            "per_token_collective_bytes": int(decode_tp_collective_bytes(
                self.cfg, t, self._compute_dtype)),
        }

    def prefill(self, src_rows, slots):
        """Encode ``src_rows (n, src_len)`` into cache rows ``slots``."""
        src_rows = np.asarray(src_rows, np.int32).reshape(-1, self.src_len)
        slots = np.asarray(slots, np.int32)
        n = len(slots)
        # largest-first greedy bucket cover: one plan execution per chunk
        done = 0
        while done < n:
            take = min(n - done, self._prefill_buckets[-1])
            pb = self._bucket(self._prefill_buckets, take)
            plan, p = self._prefill_plans[pb]
            src_pad = np.full((pb, self.src_len), self.pad_id, np.int32)
            slot_pad = np.full((pb,), self._scratch, np.int32)
            src_pad[:take] = src_rows[done:done + take]
            slot_pad[:take] = slots[done:done + take]
            self._run(plan, {p["src"]: src_pad,
                             p["slots"]: slot_pad})
            done += take

    def decode(self, tokens, positions, slots):
        """One decode position for n live sequences; returns
        (next_tok (n,), logp (n,), bucket)."""
        tokens = np.asarray(tokens, np.int32)
        positions = np.asarray(positions, np.int32)
        slots = np.asarray(slots, np.int32)
        n = len(slots)
        sb = self._bucket(self._decode_buckets, n)
        plan, p = self._decode_plans[sb]
        tok = np.full((sb,), self.pad_id, np.int32)
        pos = np.zeros((sb,), np.int32)
        slt = np.full((sb,), self._scratch, np.int32)
        tok[:n], pos[:n], slt[:n] = tokens, positions, slots
        out = self._run(plan, {p["tok"]: tok, p["pos"]: pos,
                               p["slots"]: slt})
        return (np.asarray(out["next_tok"])[:n],
                np.asarray(out["logp"])[:n], sb)

    def verify(self, tok_blocks, positions, slots):
        """Score K-token blocks ``tok_blocks (n, spec_k)`` starting at
        the committed ``positions``; returns the target's next-token
        choice at each of the K positions: (toks (n, K), logps (n, K),
        bucket). Cache rows for the block positions ARE written (the
        accepted prefix is then already materialized; rejected-suffix
        rows are dead until overwritten by the next append at that
        position, and length masking keeps attention from reading
        them)."""
        if not self._verify_plans:
            raise RuntimeError("model built without speculative_k")
        tok_blocks = np.asarray(tok_blocks, np.int32)
        positions = np.asarray(positions, np.int32)
        slots = np.asarray(slots, np.int32)
        n = len(slots)
        sb = self._bucket(sorted(self._verify_plans), n)
        plan, p = self._verify_plans[sb]
        tok = np.full((sb, self.spec_k), self.pad_id, np.int32)
        pos = np.zeros((sb,), np.int32)
        slt = np.full((sb,), self._scratch, np.int32)
        tok[:n], pos[:n], slt[:n] = tok_blocks, positions, slots
        out = self._run(plan, {p["tok"]: tok, p["pos"]: pos,
                               p["slots"]: slt})
        return (np.asarray(out["next_tok"])[:n],
                np.asarray(out["logp"])[:n], sb)

    def decode_k(self, tokens, positions, slots):
        """Draft side: run ``draft_steps`` greedy decode positions in
        one plan execution; returns (props (n, draft_steps), bucket)."""
        if not self._draft_plans:
            raise RuntimeError("model built without draft_steps")
        tokens = np.asarray(tokens, np.int32)
        positions = np.asarray(positions, np.int32)
        slots = np.asarray(slots, np.int32)
        n = len(slots)
        sb = self._bucket(sorted(self._draft_plans), n)
        plan, p = self._draft_plans[sb]
        tok = np.full((sb,), self.pad_id, np.int32)
        pos = np.zeros((sb,), np.int32)
        slt = np.full((sb,), self._scratch, np.int32)
        tok[:n], pos[:n], slt[:n] = tokens, positions, slots
        out = self._run(plan, {p["tok"]: tok, p["pos"]: pos,
                               p["slots"]: slt})
        return np.asarray(out["props"])[:n], sb

    def close(self):
        self.session.close()

    def statusz_info(self):
        info = {"decode_buckets": self._decode_buckets,
                "prefill_buckets": self._prefill_buckets,
                "num_slots": self.num_slots,
                "max_decode_len": self.max_decode_len,
                "src_len": self.src_len, "int8": self.int8,
                "sampling": self.sampling, "spec_k": self.spec_k,
                "draft_steps": self.draft_steps}
        if self.tp_degree > 1:
            info["tp"] = self.tp_info()
        return info


def synthetic_wmt_batch(batch_size, src_len, tgt_len, vocab_size=32768,
                        seed=0):
    rng = np.random.RandomState(seed)
    src = rng.randint(2, vocab_size, (batch_size, src_len)).astype(np.int32)
    tgt = rng.randint(2, vocab_size, (batch_size, tgt_len)).astype(np.int32)
    tgt_in = np.concatenate(
        [np.full((batch_size, 1), 1, np.int32), tgt[:, :-1]], axis=1)
    return {"src_ids": src, "tgt_in": tgt_in, "tgt_out": tgt}


def transformer_flops_per_token(cfg: TransformerConfig, src_len, tgt_len):
    d, ffn, L = cfg.d_model, cfg.d_ff, cfg.num_layers
    enc = L * 2 * (4 * d * d + 2 * d * ffn + 2 * src_len * d)
    dec = L * 2 * (8 * d * d + 2 * d * ffn + 2 * (src_len + tgt_len) * d)
    emb = 2 * d * cfg.vocab_size
    return (enc + dec) / 2 + emb  # rough per-token average
