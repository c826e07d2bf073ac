"""Pallas TPU kernels (replaces ref CUDA kernels, core/kernels/*_gpu.cu.cc).

Each kernel is exposed three ways:
- as a jax-level function (used directly by jax-native model code),
- as a registered graph op, so stf graph programs pick up the kernel
  through the normal Session lowering path (`stf.nn.fused_*`), and
- as a (pallas, xla) implementation pair in the stf.kernels registry:
  the graph-op lowerings below consult the registry per (op, shape,
  dtype, backend) and emit either the Pallas kernel or the stock
  composed-XLA lowering (docs/PERFORMANCE.md "kernel tier"). ``off``
  mode reproduces the pre-registry behavior exactly; ``force`` pins
  Pallas (interpret mode off-TPU, so tier-1 CPU tests run the kernels).

All kernels auto-switch to interpret mode off-TPU so the CPU test mesh
exercises identical code paths.
"""

import numpy as np

from ...framework import op_registry
from ...kernels import registry as _kreg
from .decode_attention import (decode_attention, decode_attention_xla,
                               paged_decode_attention,
                               paged_decode_attention_xla,
                               paged_heads_per_group)
from .dropout_residual import (dropout_bias_residual,
                               dropout_bias_residual_reference)
from .flash_attention import attention_xla, flash_attention, mha_reference
from .fused_update import (adam_update, adam_update_reference,
                           momentum_update, momentum_update_reference)
from .latent_attention import (paged_latent_attention,
                               paged_latent_attention_xla)
from .layer_norm import layer_norm, layer_norm_reference
from .quant_matmul import (quant_matmul, quant_matmul_reference,
                           quant_matmul_ste, quant_matmul_ste_reference,
                           quantize_colwise, quantize_rowwise)
from .softmax_xent import (softmax_cross_entropy,
                           softmax_cross_entropy_reference)
from .ssm_state_update import ssm_state_update, ssm_state_update_xla


def _np_of(dt):
    s = str(dt)
    try:
        return np.dtype(s)
    except TypeError:
        import ml_dtypes  # registered by jax; covers bfloat16 etc.

        return np.dtype(getattr(ml_dtypes, s))


def _is_float(dt) -> bool:
    s = str(dt)
    return s.startswith("float") or s.startswith("bfloat")


def _bytes_of(aval_entry):
    shape, dt = aval_entry
    n = 1
    for d in shape:
        n *= int(d)
    return n * _np_of(dt).itemsize


# ---------------------------------------------------------------------------
# FlashAttention (+Dropout): Pallas streamed kernel vs composed matmuls
# ---------------------------------------------------------------------------

def _flash_eligible(key):
    (qs, qd), (ks, _kd), (vs, _vd), bias = key[:4]
    statics = dict(key[4:])
    if not _is_float(qd):
        return "ineligible_dtype"
    if len(qs) != 4 or len(ks) != 4:
        return "ineligible_shape"
    if statics.get("causal") and qs[2] != ks[2]:
        return "ineligible_shape"
    if bias is not None:
        bs, _bd = bias
        # the kernel takes a key bias broadcast over heads/queries:
        # anything not squeezable to (batch, kv_seq) needs the composed
        # path (which handles arbitrary additive biases)
        if len(bs) < 2 or bs[0] != qs[0] or bs[-1] != ks[2] \
                or any(d != 1 for d in bs[1:-1]):
            return "ineligible_bias"
    return None


def _flash_gate(key, bk):
    (qs, qd), (ks, _), (vs, _), bias = key[:4]
    statics = dict(key[4:])
    b, h, sq, d = (int(x) for x in qs)
    sk = int(ks[2])
    flops = 4.0 * b * h * sq * sk * d * (0.5 if statics.get("causal") else 1)
    itm = _np_of(qd).itemsize
    qkv_bytes = (_bytes_of(key[0]) + _bytes_of(key[1]) + _bytes_of(key[2])
                 + b * h * sq * d * itm)
    # the composed path materializes the (B,H,Sq,Sk) f32 score matrix
    # roughly three times (scores, softmax, P·V read) — the exact HBM
    # traffic the streamed kernel exists to avoid
    return _kreg.roofline_gate(flops, qkv_bytes,
                               qkv_bytes + 3.0 * b * h * sq * sk * 4, bk)


_kreg.register_kernel(
    "FlashAttention",
    impls={"pallas": flash_attention, "xla": attention_xla},
    legacy="pallas",
    eligible=_flash_eligible,
    cost_gate=_flash_gate,
    graph_key=lambda op: _flash_graph_key(op),
    doc="streamed FlashAttention-2 kernel vs composed batch-matmul "
        "attention")
_kreg.register_kernel(
    "FlashAttentionDropout",
    impls={"pallas": flash_attention, "xla": attention_xla},
    legacy="pallas",
    eligible=_flash_eligible,
    cost_gate=_flash_gate,
    graph_key=lambda op: _flash_graph_key(op, dropout=True),
    doc="FlashAttention with in-kernel probability dropout (counter-"
        "based mask shared with the composed fallback)")


def _tensor_aval(t):
    sh = t.shape
    if sh.rank is None or any(d.value is None for d in sh.dims):
        return None
    return (tuple(int(d.value) for d in sh.dims), t.dtype.base_dtype.name)


def _flash_graph_key(op, dropout=False):
    avals = [_tensor_aval(t) for t in op.inputs]
    if any(a is None for a in avals[:3]) or len(avals) < 3:
        return None
    bias = avals[3] if len(avals) > 3 else None
    return _kreg.aval_key(
        *[_Aval(*a) for a in avals[:3]],
        *( [_Aval(*bias)] if bias is not None else [None]),
        causal=bool(op.attrs.get("causal", False)), dropout=bool(dropout))


class _Aval:
    """shape/dtype carrier for aval_key from graph tensors."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = shape
        self.dtype = dtype


def _flash_key(q, k, v, bias, causal, dropout):
    return _kreg.aval_key(q, k, v, bias, causal=bool(causal),
                          dropout=bool(dropout))


def _lower_flash(ctx, op, input_values):
    q, k, v = input_values[:3]
    bias = input_values[3] if len(input_values) > 3 else None
    causal = op.attrs.get("causal", False)
    sm_scale = op.attrs.get("sm_scale")
    fn = _kreg.select("FlashAttention",
                      _flash_key(q, k, v, bias, causal, False))
    return [fn(q, k, v, bias=bias, causal=causal, sm_scale=sm_scale)]


def _flash_dropout_lower(ctx, op, input_values):
    """FlashAttention with probability dropout: stateful (never CSE'd —
    two dropout sites must draw different masks), seeded from the op's
    per-step RNG stream so fwd and vjp replay the same mask. The op's
    graph/op seed attrs fold into the stream exactly like nn_ops
    dropout (random_seed.fold_in_value), so ``stf.set_random_seed``
    reproduces the mask regardless of op naming — and regardless of
    which implementation the registry picks (both draw the identical
    counter-based mask from the derived seed)."""
    import jax
    import jax.numpy as jnp

    q, k, v = input_values[:3]
    bias = input_values[3] if len(input_values) > 3 else None
    key = ctx.rng_for(op)
    seed = jax.random.randint(key, (1,), 0, jnp.iinfo(jnp.int32).max,
                              dtype=jnp.int32)
    causal = op.attrs.get("causal", False)
    fn = _kreg.select("FlashAttentionDropout",
                      _flash_key(q, k, v, bias, causal, True))
    out = fn(q, k, v, bias=bias, causal=causal,
             sm_scale=op.attrs.get("sm_scale"),
             dropout_rate=float(op.attrs["dropout_rate"]), dropout_seed=seed)
    return [out]


op_registry.register("FlashAttention", lower=_lower_flash)
op_registry.register("FlashAttentionDropout", lower=_flash_dropout_lower,
                     effects=op_registry.Effects(rng=True))


# ---------------------------------------------------------------------------
# FusedLayerNorm: one-pass VMEM kernel vs composed mean/var/normalize
# ---------------------------------------------------------------------------

def _ln_eligible(key):
    (xs, xd), (gs, _), (bs, _) = key[:3]
    if not _is_float(xd):
        return "ineligible_dtype"
    if len(xs) < 1 or len(gs) != 1 or len(bs) != 1 or gs[0] != xs[-1]:
        return "ineligible_shape"
    return None


def _ln_gate(key, bk):
    xb = _bytes_of(key[0])
    n = 1
    for d in key[0][0]:
        n *= int(d)
    # composed LN re-reads x for the mean pass, the variance pass and
    # the normalize/affine pass (pre-fusion accounting); the kernel
    # streams each row block once
    return _kreg.roofline_gate(5.0 * n, 2.0 * xb, 4.0 * xb, bk)


_kreg.register_kernel(
    "FusedLayerNorm",
    impls={"pallas": layer_norm, "xla": layer_norm_reference},
    legacy="pallas",
    eligible=_ln_eligible,
    cost_gate=_ln_gate,
    graph_key=lambda op: _simple_graph_key(op),
    doc="one-pass fused layer norm vs composed mean/var/normalize")


def _simple_graph_key(op, **statics):
    avals = [_tensor_aval(t) for t in op.inputs]
    if any(a is None for a in avals):
        return None
    return _kreg.aval_key(*[_Aval(*a) for a in avals], **statics)


def _lower_fused_layer_norm(ctx, op, inputs):
    x, gamma, beta = inputs
    eps = float(op.attrs.get("eps", 1e-6))
    fn = _kreg.select("FusedLayerNorm", _kreg.aval_key(x, gamma, beta))
    return [fn(x, gamma, beta, eps=eps)]


op_registry.register("FusedLayerNorm", lower=_lower_fused_layer_norm)


# ---------------------------------------------------------------------------
# FusedSoftmaxXent: streamed online-softmax xent vs composed log_softmax
# ---------------------------------------------------------------------------

def _xent_eligible(key):
    (ls, ld), (labs, labd) = key[:2]
    if not _is_float(ld) or _np_of(labd).kind not in "iu":
        return "ineligible_dtype"
    if len(ls) < 1 or len(labs) != len(ls) - 1:
        return "ineligible_shape"
    return None


def _xent_gate(key, bk):
    lb = _bytes_of(key[0])
    n = 1
    for d in key[0][0]:
        n *= int(d)
    # composed materializes log_softmax at [rows, vocab] f32 (plus the
    # max/sum passes); the kernel streams each row's vocab blocks once
    return _kreg.roofline_gate(5.0 * n, 1.2 * lb, 3.0 * lb, bk)


_kreg.register_kernel(
    "FusedSoftmaxXent",
    impls={"pallas": softmax_cross_entropy,
           "xla": softmax_cross_entropy_reference},
    legacy="pallas",
    eligible=_xent_eligible,
    cost_gate=_xent_gate,
    graph_key=lambda op: _simple_graph_key(op),
    doc="streamed sparse softmax-xent vs composed log_softmax + gather")


def _lower_fused_xent(ctx, op, inputs):
    logits, labels = inputs
    sm = float(op.attrs.get("label_smoothing", 0.0))
    fn = _kreg.select(
        "FusedSoftmaxXent",
        _kreg.aval_key(logits, labels, label_smoothing=sm > 0.0))
    return [fn(logits, labels, label_smoothing=sm)]


op_registry.register("FusedSoftmaxXent", lower=_lower_fused_xent)


# ---------------------------------------------------------------------------
# QuantMatMul: native int8 MXU kernel vs int32 jnp dot
# ---------------------------------------------------------------------------

def _qmm_eligible(key):
    (xs, xd), (ws, wd), (ss, _sd) = key[:3]
    if not _is_float(xd) or str(wd) != "int8":
        return "ineligible_dtype"
    if len(xs) != 2 or len(ws) != 2 or len(ss) != 1:
        return "ineligible_shape"
    return None


def _qmm_gate(key, bk):
    if bk != "tpu":
        return ("xla", "interpret_backend")
    # the MXU multiplies int8 natively at 2x the bf16 rate; XLA lowers
    # the int32 jnp.dot off that fast path — the kernel wins whenever
    # the matmul is big enough to be MXU-bound at all
    (xs, _), (ws, _), _ = key[:3]
    m, k = int(xs[0]), int(xs[1])
    n = int(ws[1])
    if 2.0 * m * k * n >= 1e8:
        return ("pallas", "cost_model")
    return (None, "cost_model_uncertain")


_kreg.register_kernel(
    "QuantMatMul",
    impls={"pallas": quant_matmul_ste, "xla": quant_matmul_ste_reference},
    legacy="pallas",
    eligible=_qmm_eligible,
    cost_gate=_qmm_gate,
    graph_key=lambda op: _simple_graph_key(op),
    doc="int8 MXU quantized matmul (straight-through vjp) vs int32 dot")


def _lower_quant_matmul(ctx, op, inputs):
    x, wq, w_scale = inputs
    fn = _kreg.select("QuantMatMul", _kreg.aval_key(x, wq, w_scale))
    return [fn(x, wq, w_scale)]


op_registry.register("QuantMatMul", lower=_lower_quant_matmul)


# ---------------------------------------------------------------------------
# FusedDropoutBiasResidual: blocked elementwise kernel vs fused XLA chain.
# XLA fuses a pure elementwise chain into one pass itself, so the static
# gate prefers the composed lowering; the kernel runs only under
# ``force`` (ROADMAP S11 (a)).
# ---------------------------------------------------------------------------

def _dbr_eligible(key):
    (xs, xd), (rs, _rd), bias = key[:3]
    if not _is_float(xd):
        return "ineligible_dtype"
    if tuple(xs) != tuple(rs) or len(xs) < 1:
        return "ineligible_shape"
    if bias is not None and (len(bias[0]) != 1 or bias[0][0] != xs[-1]):
        return "ineligible_shape"
    return None


def _dbr_gate(key, bk):
    if bk != "tpu":
        return ("xla", "interpret_backend")
    # elementwise: both lowerings are one HBM pass (XLA fuses the
    # composed chain); nothing for the kernel to win statically
    return ("xla", "cost_model")


def _dbr_pallas(x, residual, bias=None, *, rate, seed):
    return dropout_bias_residual(x, residual, bias, rate=rate, seed=seed)


def _dbr_xla(x, residual, bias=None, *, rate, seed):
    return dropout_bias_residual_reference(x, residual, bias, rate=rate,
                                           seed=seed)


_kreg.register_kernel(
    "FusedDropoutBiasResidual",
    impls={"pallas": _dbr_pallas, "xla": _dbr_xla},
    legacy="xla",
    eligible=_dbr_eligible,
    cost_gate=_dbr_gate,
    graph_key=lambda op: _dbr_graph_key(op),
    doc="fused residual + dropout(x + bias) vs composed elementwise "
        "chain (identical counter-based mask)")


def _dbr_graph_key(op):
    avals = [_tensor_aval(t) for t in op.inputs]
    if len(avals) < 2 or any(a is None for a in avals):
        return None
    bias = avals[2] if len(avals) > 2 else None
    return _kreg.aval_key(_Aval(*avals[0]), _Aval(*avals[1]),
                          _Aval(*bias) if bias is not None else None,
                          rate=float(op.attrs.get("rate", 0.0)))


def _lower_dropout_bias_residual(ctx, op, inputs):
    import jax
    import jax.numpy as jnp

    x, residual = inputs[:2]
    bias = inputs[2] if len(inputs) > 2 else None
    rate = float(op.attrs["rate"])
    key = ctx.rng_for(op)
    seed = jax.random.randint(key, (1,), 0, jnp.iinfo(jnp.int32).max,
                              dtype=jnp.int32)
    fn = _kreg.select(
        "FusedDropoutBiasResidual",
        _kreg.aval_key(x, residual, bias, rate=rate))
    return [fn(x, residual, bias, rate=rate, seed=seed)]


op_registry.register("FusedDropoutBiasResidual",
                     lower=_lower_dropout_bias_residual,
                     effects=op_registry.Effects(rng=True))


# ---------------------------------------------------------------------------
# Fused optimizer updates: the flat-group math pairs. The graph ops
# (FusedAdamUpdate / FusedMomentumUpdate) are registered by
# train/optimizers.py, which owns their variable semantics; it routes
# each flat group through these registry entries.
# ---------------------------------------------------------------------------

def _flat_gate(key, bk):
    if bk != "tpu":
        return ("xla", "interpret_backend")
    n = int(dict(key).get("n", 0))
    # one guaranteed pass over the g/m/v/p streams; below ~1M elements
    # launch overhead and XLA's own fusion make it a wash — abstain
    if n >= (1 << 20):
        return ("pallas", "cost_model")
    return (None, "cost_model_uncertain")


_kreg.register_kernel(
    "FusedAdamUpdate",
    impls={"pallas": adam_update, "xla": adam_update_reference},
    legacy="xla",
    cost_gate=_flat_gate,
    graph_key=lambda op: _opt_graph_key(op),
    doc="one flat m/v/param Adam update per dtype group vs the fused "
        "XLA closure")
_kreg.register_kernel(
    "FusedMomentumUpdate",
    impls={"pallas": momentum_update, "xla": momentum_update_reference},
    legacy="xla",
    cost_gate=_flat_gate,
    graph_key=lambda op: _opt_graph_key(op),
    doc="one flat accumulator/param Momentum update per dtype group vs "
        "the fused XLA closure")


def _opt_graph_key(op):
    n = 0
    for t in op.inputs:
        a = _tensor_aval(t)
        if a is None:
            return None
        sz = 1
        for d in a[0]:
            sz *= d
        n += sz
    return _kreg.aval_key(n=int(n), pdt="float32", udt="float32")


def flat_group_key(n, pdt, udt):
    """Decision key for one flattened optimizer parameter group."""
    return _kreg.aval_key(n=int(n), pdt=str(pdt), udt=str(udt))


# ---------------------------------------------------------------------------
# DecodeAttention: paged-cache decode kernel (q length 1) vs composed
# masked softmax. The graph op is registered by ops/kv_cache_ops.py,
# which owns the cache semantics; this entry owns the routing.
# ---------------------------------------------------------------------------

def _decode_attn_eligible(key):
    (qs, qd), (ks, _kd), (vs, _vd), bias = key[:4]
    if not _is_float(qd):
        return "ineligible_dtype"
    # q is (B, H, D) — or the (B, Kq, H, D) query block of the
    # speculative-verify / block-prefill plans
    if len(qs) not in (3, 4) or len(ks) != 4 or len(vs) != 4:
        return "ineligible_shape"
    if ks[0] != qs[0] or ks[2] != qs[-2] or ks[3] != qs[-1] or ks != vs:
        return "ineligible_shape"
    if bias is not None:
        bs, _bd = bias
        if len(bs) != 2 or bs[0] != qs[0] or bs[1] != ks[1]:
            return "ineligible_bias"
    return None


def _decode_attn_gate(key, bk):
    (qs, qd), (ks, _), _, _bias = key[:4]
    b, h, d = int(qs[0]), int(qs[-2]), int(qs[-1])
    kq = int(qs[1]) if len(qs) == 4 else 1
    max_len = int(ks[1])
    flops = 4.0 * b * kq * h * max_len * d
    itm = _np_of(qd).itemsize
    cache_bytes = 2.0 * b * max_len * h * d * itm
    # composed materializes the (B[, Kq], H, L) f32 score tensor ~three
    # times (scores, softmax, P·V read); the kernel streams the cache
    # once
    return _kreg.roofline_gate(
        flops, cache_bytes + b * kq * h * d * itm,
        cache_bytes + 3.0 * b * kq * h * max_len * 4, bk)


_kreg.register_kernel(
    "DecodeAttention",
    impls={"pallas": decode_attention, "xla": decode_attention_xla},
    legacy="xla",
    eligible=_decode_attn_eligible,
    cost_gate=_decode_attn_gate,
    graph_key=lambda op: _decode_attn_graph_key(op),
    doc="paged-cache decode attention (query length 1, heads on the "
        "sublane axis) vs composed masked softmax")


def _decode_attn_graph_key(op):
    avals = [_tensor_aval(t) for t in op.inputs[:3]]
    if len(avals) < 3 or any(a is None for a in avals):
        return None
    bias = _tensor_aval(op.inputs[4]) if len(op.inputs) > 4 else None
    if len(op.inputs) > 4 and bias is None:
        return None
    return _kreg.aval_key(
        *[_Aval(*a) for a in avals],
        _Aval(*bias) if bias is not None else None,
        has_bias=len(op.inputs) > 4)


# ---------------------------------------------------------------------------
# PagedDecodeAttention: K and V pages read from the stored pool through
# the page table vs the gathered logical view + composed masked softmax.
# The graph op is registered by ops/kv_cache_ops.py; this entry owns the
# routing.
# ---------------------------------------------------------------------------

def _paged_attn_eligible(key):
    (qs, qd), (ps, pd), (ts, _td) = key[:3]
    if not _is_float(qd) or str(pd) != str(qd):
        return "ineligible_dtype"
    # q (B, H, D) or (B, Kq, H, D); pool (pages, page_len, H_kv*D) as
    # stored; tables (B, n_blocks)
    if len(qs) not in (3, 4) or len(ps) != 3 or len(ts) != 2:
        return "ineligible_shape"
    if ts[0] != qs[0] or ps[2] % qs[-1] \
            or (qs[-2] * qs[-1]) % ps[2]:
        return "ineligible_shape"
    # grouped queries (H > H_kv): a key-value head's lanes are sliced
    # out of the page, so they are whole 128-lane tiles
    if ps[2] != qs[-2] * qs[-1] and qs[-1] % 128:
        return "ineligible_shape"
    return None


def _paged_attn_gate(key, bk):
    (qs, qd), (ps, _), (ts, _) = key[:3]
    b, h, d = int(qs[0]), int(qs[-2]), int(qs[-1])
    kq = int(qs[1]) if len(qs) == 4 else 1
    page_len, n_blocks = int(ps[1]), int(ts[1])
    itm = _np_of(qd).itemsize
    view_len = n_blocks * page_len
    h_kv = int(ps[2]) // d
    # the kernel's MXU work: block-diagonal queries spend ``group``
    # times the flops (grouped queries none: a key-value head's query
    # heads are rows of its own tile); charged to both sides, so the
    # bytes decide
    group = paged_heads_per_group(kq, h, d) if h_kv == h else 1
    flops = 4.0 * group * b * kq * h * view_len * d
    q_out = 2.0 * b * kq * h * d * itm
    # K and V views of every table entry: the most the kernel reads
    # (entries past a row's length are skipped at run time)
    views = 2.0 * b * view_len * h_kv * d * itm
    # the composition: the gather reads and writes both views, the
    # (B, L, H, D) relayout reads them and writes them with head_dim
    # padded to the 128-lane tile, attention reads that, and the
    # (B, Kq, H, L) float32 scores make three passes
    pad = max(1.0, 128.0 / d)
    composed = views * (2.0 + 1.0 + 2.0 * pad) \
        + 3.0 * b * kq * h * view_len * 4
    return _kreg.roofline_gate(flops, views + q_out, composed + q_out, bk)


_kreg.register_kernel(
    "PagedDecodeAttention",
    impls={"pallas": paged_decode_attention,
           "xla": paged_decode_attention_xla},
    legacy="xla",
    eligible=_paged_attn_eligible,
    cost_gate=_paged_attn_gate,
    graph_key=lambda op: _paged_attn_graph_key(op),
    doc="decode attention over K/V pages read in place through the page "
        "table vs the gathered logical view + composed masked softmax")


def _paged_attn_graph_key(op):
    from .. import kv_cache_ops as _kvc

    q, tables = _tensor_aval(op.inputs[0]), _tensor_aval(op.inputs[1])
    if q is None or tables is None:
        return None
    pool = _Aval(_kvc.stored_shape(op.attrs["shape"]), q[1])
    return _kreg.aval_key(_Aval(*q), pool, _Aval(*tables))


# ---------------------------------------------------------------------------
# PagedLatentAttention: absorbed latent attention over ONE pool of latent
# rows read in place through the page table vs the gathered view +
# composed masked softmax. The graph op is registered by
# ops/kv_cache_ops.py; this entry owns the routing.
# ---------------------------------------------------------------------------

def _latent_attn_eligible(key):
    (qs, qd), (ps, pd), (ts, _td) = key[:3]
    if not _is_float(qd) or str(pd) != str(qd):
        return "ineligible_dtype"
    # q (B, H, W) or (B, Kq, H, W); pool (pages, page_len, W) as stored;
    # tables (B, n_blocks)
    if len(qs) not in (3, 4) or len(ps) != 3 or len(ts) != 2:
        return "ineligible_shape"
    if ts[0] != qs[0] or ps[2] != qs[-1]:
        return "ineligible_shape"
    return None


def _latent_attn_gate(key, bk):
    (qs, qd), (ps, _), (ts, _) = key[:3]
    value_dim = dict(key[3:])["value_dim"]
    b, h, w = int(qs[0]), int(qs[-2]), int(qs[-1])
    kq = int(qs[1]) if len(qs) == 4 else 1
    page_len, n_blocks = int(ps[1]), int(ts[1])
    itm = _np_of(qd).itemsize
    view_len = n_blocks * page_len
    flops = 2.0 * b * kq * h * view_len * (w + value_dim)
    q_out = b * kq * h * (w + value_dim) * itm
    # every table entry's rows once: the most the kernel reads (entries
    # past a row's length are skipped at run time)
    view = 1.0 * b * view_len * w * itm
    # the composition gathers the view (read + write), reads it for the
    # scores and again for the values, and its (B, Kq, H, L) float32
    # scores make three passes
    composed = view * 4.0 + 3.0 * b * kq * h * view_len * 4
    return _kreg.roofline_gate(flops, view + q_out, composed + q_out, bk)


_kreg.register_kernel(
    "PagedLatentAttention",
    impls={"pallas": paged_latent_attention,
           "xla": paged_latent_attention_xla},
    legacy="xla",
    eligible=_latent_attn_eligible,
    cost_gate=_latent_attn_gate,
    graph_key=lambda op: _latent_attn_graph_key(op),
    doc="absorbed latent attention over one pool of latent rows read in "
        "place through the page table vs the gathered view + composed "
        "masked softmax")


def _latent_attn_graph_key(op):
    from .. import kv_cache_ops as _kvc

    q, tables = _tensor_aval(op.inputs[0]), _tensor_aval(op.inputs[1])
    if q is None or tables is None:
        return None
    pool = _Aval(_kvc.stored_shape(op.attrs["shape"]), q[1])
    return _kreg.aval_key(_Aval(*q), pool, _Aval(*tables),
                          value_dim=int(op.attrs["value_dim"]))


# ---------------------------------------------------------------------------
# SSMStateUpdate: one decode token of a state-space layer, every row's
# state read and written in place in the slot pool vs gather, update and
# scatter. The graph op is registered by ops/ssm_ops.py; this entry owns
# the routing.
# ---------------------------------------------------------------------------

def _ssm_update_eligible(key):
    (xs, xd), (ps, pd), (bs, _bd) = key[:3]
    if not _is_float(xd) or str(pd) != "float32":
        return "ineligible_dtype"
    # x (B, H, P); pool (slots, H / pack, N, pack * P); B (B, G, N)
    if len(xs) != 3 or len(ps) != 4 or len(bs) != 3:
        return "ineligible_shape"
    if ps[3] % xs[2] or ps[1] * (ps[3] // xs[2]) != xs[1] \
            or ps[2] != bs[2] or xs[1] % bs[1]:
        return "ineligible_shape"
    return None


def _ssm_update_gate(key, bk):
    (xs, _xd), (ps, _pd) = key[:2]
    rows = float(xs[0])
    state = 4.0 * ps[1] * ps[2] * ps[3]
    # the kernel reads and writes a row's state once; the composition
    # gathers it (read + write), updates it (read + write) and scatters
    # it (read + write)
    return _kreg.roofline_gate(6.0 * rows * state / 4.0, 2.0 * rows * state,
                               6.0 * rows * state, bk)


_kreg.register_kernel(
    "SSMStateUpdate",
    impls={"pallas": ssm_state_update, "xla": ssm_state_update_xla},
    legacy="xla",
    eligible=_ssm_update_eligible,
    cost_gate=_ssm_update_gate,
    graph_key=lambda op: _ssm_update_graph_key(op),
    doc="one decode token of a state-space layer, the rows' states "
        "updated in place in the slot pool vs gather, update and scatter")


def _ssm_update_graph_key(op):
    x, bm = _tensor_aval(op.inputs[0]), _tensor_aval(op.inputs[3])
    if x is None or bm is None:
        return None
    return _kreg.aval_key(_Aval(*x), _Aval(tuple(op.attrs["shape"]),
                                           "float32"), _Aval(*bm))
