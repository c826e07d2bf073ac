"""The plain reference of the short-convolution routed-FFN decoder (LFM2's
mixture-of-experts block).

float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
written from the layer equations (PERF.md section 4), independent
of ``simple_tensorflow_tpu``: it imports nothing of the program. A full
forward pass over prompt + served tokens: no cache, no chunks, no slots, no
kernels, no batching. The convolution is a padded sum of shifted copies,
attention a plain causal softmax in blocks of queries, the experts a plain
loop over all of them. Helpers the other references already have are
imported from them.

Every layer ``h <- h + mixer(RMSNorm(h))``, ``h <- h + ffn(RMSNorm(h))``;
after the last the final RMSNorm and the head TIED to the embedding
(``logits = h embed^T``).

- mixer ``conv``: ``[B | C | x~] = u.w_in`` (thirds in that order); ``v =
  conv(B * x~)``, ``sum_k conv_w[k] in[t - (taps - 1) + k]`` (no bias,
  zeros before the sequence); ``out = (C * v).w_out``.
- mixer ``full_attention``: ``q = u.wq`` (heads x head_dim), ``k = u.wk``,
  ``v = u.wv`` (kv_heads x head_dim); RMSNorm over head_dim on q (``q_norm``)
  and k (``k_norm``); RoPE (rotate half, ``rope_theta``); head h reads
  key-value head ``h // (heads / kv_heads)``, ``softmax(q.k /
  sqrt(head_dim))`` causal; ``out = concat(o).wo``.
- feed-forward of the first ``dense_layers`` layers: ``(silu(b.W1) *
  b.W3).W2`` of ``dense_width`` (``wf_gate_up = [W1 | W3]``); after them:
  ``s = sigmoid(b.wr)`` over ALL ``experts``, the top ``experts_per_token``
  by ``s + bias``, gates ``s`` over their sum (+ ``gate_eps``) x
  ``gate_scale``; the experts' SwiGLUs of ``expert_width``; no shared
  expert.

Weights are made from the seed a LEAF at a time
(``hybrid_linear_moe_decoder._maker``: a leaf of experts an expert at a
time) and every sequence is taken through a layer before the next is made.
The embedding is drawn ``N(0, 0.02^2)``, as tied embeddings are
initialised: at unit scale a position's own row would dominate the tied
head's logits and every answer repeat its last token.
Leaves in ``spec["bf16_leaves"]`` hold bfloat16-representable values, as
the configuration stores them.

``precision``: ``"f32"`` is the reference; ``"fp8"`` the CONTROL: both
operands of every matmul rounded to float8_e4m3, per-tensor scaled (never
the router's product, which picks experts, nor the convolution, which is
elementwise).
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.hybrid_linear_moe_decoder import _maker, _rule, conv
from chipbench.reference.latent_moe_decoder import seed_key
from chipbench.reference.sparse_moe_decoder import (
    _HI, _einsum, _rms_norm, _rope, QUERY_BLOCK)

_NEG_INF = float("-inf")
CONV = "conv"


def layer_leaf_shapes(spec, i):
    d = spec["hidden"]
    out = {"ln1": (d,), "ln2": (d,)}
    if spec["layer_types"][i] == CONV:
        out.update(w_in=(d, 3 * d), conv_w=(spec["conv_taps"], d),
                   w_out=(d, d))
    else:
        h, kv, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
        out.update(wq=(d, h * hd), wk=(d, kv * hd), wv=(d, kv * hd),
                   q_norm=(hd,), k_norm=(hd,), wo=(h * hd, d))
    if i < spec["dense_layers"]:
        wf = spec["dense_width"]
        out.update(wf_gate_up=(d, 2 * wf), wf_down=(wf, d))
    else:
        e, w = spec["experts"], spec["expert_width"]
        out.update(wr=(d, e), bias=(e,), w_gate_up=(e, d, 2 * w),
                   w_down=(e, w, d))
    return out


def top_leaf_shapes(spec):
    d, v = spec["hidden"], spec["vocab"]
    return {"embed": (v, d), "final_norm": (d,)}


def _rule_of(name, shape):
    """How a leaf is drawn (``hybrid_linear_moe_decoder._rule``), the
    embedding small."""
    return "small" if name == "embed" else _rule(name, shape)


def _leaf_makers(spec, shapes, key, prefix, stored):
    """``[(name, make)]``: each leaf's weights when ``make()`` is called
    (the j-th leaf by name from ``fold_in(key, j)``)."""
    out = []
    for j, name in enumerate(sorted(shapes)):
        shape = tuple(shapes[name])
        make = _maker(shape, _rule_of(name, shape),
                      prefix + name in spec.get("bf16_leaves", ()), stored,
                      float(spec["bias_std"]), None)
        out.append((name, functools.partial(make, jax.random.fold_in(key,
                                                                      j))))
    return out


def layer_leaf_makers(spec, seed, i, stored=False):
    """Layer ``i``'s leaves, made one at a time on the default device:
    float32 arrays, or with ``stored`` the ``bf16_leaves`` as bfloat16
    arrays of the same values."""
    return _leaf_makers(spec, layer_leaf_shapes(spec, i),
                        jax.random.fold_in(seed_key(seed), i + 1),
                        "layers.", stored)


def top_leaf_makers(spec, seed, stored=False):
    """The embedding (the head too) and the final norm."""
    return _leaf_makers(spec, top_leaf_shapes(spec),
                        jax.random.fold_in(seed_key(seed), 0), "", stored)


def init_layer(spec, seed, i, stored=False):
    return {name: make() for name, make in
            layer_leaf_makers(spec, seed, i, stored)}


def init_top(spec, seed, stored=False):
    return {name: make() for name, make in
            top_leaf_makers(spec, seed, stored)}


# -- the mathematics ---------------------------------------------------------

def short_conv(u, lp, spec, precision="f32"):
    """``u (S, d)`` normed -> the gated short convolution ``(S, d)``."""
    d = spec["hidden"]
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)
    bcx = mm(u, lp["w_in"])
    b, c, x = (bcx[:, j * d:(j + 1) * d] for j in range(3))
    return mm(c * conv(b * x, lp["conv_w"]), lp["w_out"])


def attention(u, lp, spec, precision="f32"):
    """``u (S, d)`` normed -> ``(S, d)``; S is a multiple of
    QUERY_BLOCK."""
    s_len = u.shape[0]
    h, kv, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
    theta, eps = spec["rope_theta"], spec["rms_eps"]
    pos = jnp.arange(s_len)
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)
    q = _rope(_rms_norm(mm(u, lp["wq"]).reshape(s_len, h, hd),
                        lp["q_norm"], eps), pos, theta)
    k = _rope(_rms_norm(mm(u, lp["wk"]).reshape(s_len, kv, hd),
                        lp["k_norm"], eps), pos, theta)
    v = mm(u, lp["wv"]).reshape(s_len, kv, hd)

    def block(start):
        t = start + jnp.arange(QUERY_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK).reshape(
            QUERY_BLOCK, kv, h // kv, hd)
        logits = _einsum("qgrd,sgd->grqs", qb, k, precision) * hd ** -0.5
        seen = pos[None, :] <= t[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], logits, _NEG_INF),
                           axis=-1)
        return _einsum("grqs,sgd->qgrd", p, v, precision).reshape(
            QUERY_BLOCK, h * hd)

    o = jax.lax.map(block, jnp.arange(0, s_len, QUERY_BLOCK))
    return mm(o.reshape(s_len, h * hd), lp["wo"])


def _swiglu(b, w_gate_up, w_down, precision):
    width = w_down.shape[0]
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)
    return mm(jax.nn.silu(mm(b, w_gate_up[:, :width]))
              * mm(b, w_gate_up[:, width:]), w_down)


def route(b, wr, bias, spec):
    """Gates ``(S, experts)``: ``s_e / (sum_E s + gate_eps) * gate_scale``
    on the token's top-k experts BY ``s + bias``, 0 elsewhere. Never
    rounded for the control: it picks experts."""
    s = jax.nn.sigmoid(jnp.dot(b, wr, precision=_HI))
    _, top_e = jax.lax.top_k(s + bias, spec["experts_per_token"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if spec["norm_topk"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + spec["gate_eps"])
    rows = jnp.arange(b.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, top_e].set(top_s * spec["gate_scale"])


def routed(b, lp, spec, precision="f32"):
    """``sum_{e in E} g_e E_e(b)``, a loop over every expert."""
    gates = route(b, lp["wr"], lp["bias"], spec)

    def expert(y, e):
        out = _swiglu(b, lp["w_gate_up"][e], lp["w_down"][e], precision)
        return y + gates[:, e, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(b),
                        jnp.arange(spec["experts"]))
    return y


@functools.partial(jax.jit, static_argnames=("spec_json", "precision",
                                             "kind"))
def _layer(x, lp, spec_json, precision, kind):
    spec = json.loads(spec_json)
    eps = spec["rms_eps"]
    mixer, dense = kind
    u = _rms_norm(x, lp["ln1"], eps)
    x = x + (short_conv if mixer == CONV else attention)(u, lp, spec,
                                                         precision)
    b = _rms_norm(x, lp["ln2"], eps)
    if dense:
        return x + _swiglu(b, lp["wf_gate_up"], lp["wf_down"], precision)
    return x + routed(b, lp, spec, precision)


@functools.partial(jax.jit, static_argnames=("spec_json", "precision"))
def _head(x, positions, top, spec_json, precision):
    spec = json.loads(spec_json)
    h = _rms_norm(x[positions], top["final_norm"], spec["rms_eps"])
    return _einsum("sd,vd->sv", h, top["embed"], precision)


def logits_at(spec, seed, seqs, positions, precision="f32", timings=None):
    """Full forward over each of ``seqs`` (1-D id arrays), a layer at a
    time over all of them; returns, per sequence, the logits ``(len(p),
    vocab)`` at its ``positions`` p. ``timings``: a dict that gets the
    seconds spent making weights and in the layers (each synced)."""
    spec_json = json.dumps(spec, sort_keys=True)
    spent = {"weights_s": 0.0, "layers_s": 0.0, "head_s": 0.0}

    def timed(key, fn, *args, **kw):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kw))
        spent[key] += time.perf_counter() - t
        return out

    with jax.default_matmul_precision("highest"):
        top = timed("weights_s", init_top, spec, seed)
        # every sequence padded behind its last token (never seen) to one
        # length: one program a kind of layer, not one a sequence
        length = -(-max(len(ids) for ids in seqs) // QUERY_BLOCK) \
            * QUERY_BLOCK
        hidden = [top["embed"][jnp.asarray(np.pad(
            np.asarray(ids, np.int32), (0, length - len(ids))))]
            for ids in seqs]
        for i, mixer in enumerate(spec["layer_types"]):
            lp = timed("weights_s", init_layer, spec, seed, i)
            kind = (mixer, i < spec["dense_layers"])
            hidden = [timed("layers_s", _layer, x, lp, spec_json, precision,
                            kind=kind) for x in hidden]
            del lp
        out = [timed("head_s", _head, x, jnp.asarray(p, jnp.int32), top,
                     spec_json, precision)
               for x, p in zip(hidden, positions)]
    if timings is not None:
        for key, value in spent.items():
            timings[key] = timings.get(key, 0.0) + value
    return out


def served_token_gaps(spec, seed, prompts, served, control=None,
                      timings=None):
    """What ``hybrid_linear_moe_decoder.served_token_gaps`` returns, for
    this model: per request a dict of arrays over the positions that
    emitted a served token — ``gap`` (best logit minus the served
    token's), ``logprob`` (of the served token), ``margin`` (best minus
    second), ``second`` (the second-best token); with ``control`` (a
    precision name) also ``control_gap`` and ``control_logprob``."""
    seqs = [list(p) + list(s) for p, s in zip(prompts, served)]
    # the position that emitted served token j is len(prompt) - 1 + j
    positions = [len(p) - 1 + np.arange(len(s))
                 for p, s in zip(prompts, served)]
    ref = logits_at(spec, seed, seqs, positions, timings=timings)
    low = logits_at(spec, seed, seqs, positions, control) if control else None
    at = lambda logits, t: jnp.take_along_axis(  # noqa: E731
        logits, t[:, None], 1)[:, 0]
    out = []
    for n, toks in enumerate(served):
        tok = jnp.asarray(np.asarray(toks, np.int32))
        best2, best2_tok = jax.lax.top_k(ref[n], 2)
        row = {"gap": best2[:, 0] - at(ref[n], tok),
               "logprob": at(jax.nn.log_softmax(ref[n]), tok),
               "margin": best2[:, 0] - best2[:, 1],
               "second": best2_tok[:, 1]}
        if low is not None:
            row["control_gap"] = best2[:, 0] - at(ref[n],
                                                  jnp.argmax(low[n], -1))
            row["control_logprob"] = at(jax.nn.log_softmax(low[n]), tok)
        out.append({k: np.asarray(v) for k, v in row.items()})
    return out
