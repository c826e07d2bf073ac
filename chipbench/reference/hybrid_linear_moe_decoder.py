"""The plain reference of the hybrid linear-attention routed-FFN decoder
(GLM-5.3-Flash's block), as ONE CHIP'S SHARE of a deployment that divides
every routed layer's experts over several chips.

float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
written from the layer equations (PERF.md section 4; ISSUE 39), independent
of ``simple_tensorflow_tpu``: it imports nothing of the program. A full
forward pass over prompt + served tokens: no cache, no chunks, no slots, no
pooled-key cache, no kernels, no batching. The KDA recurrence is a
``lax.scan`` over TOKENS, the convolution a padded sum of shifted copies,
sparse attention scores EVERY block for every query and masks the plain
softmax, the experts a plain loop over the ones held. Helpers the other
references already have are imported from them.

Every sublayer F sits in the hyper-connected residual of ``hc_mult`` = n
streams ``X (n, hidden)`` (the embedding copied to each): ``x~ =
RMSNorm(vec X)`` (no gain, ``rms_eps``); ``H_pre = sigmoid(s0 x~.phi_pre +
b_pre)``, ``H_post = 2 sigmoid(s1 x~.phi_post + b_post)``, ``H_res =
Sinkhorn(exp(s2 x~.phi_res + b_res))`` — ``hc_iters`` times a row then a
column normalisation, each sum ``+ hc_eps``; ``X <- H_res X + H_post^T
F(RMSNorm(H_pre X))``. After the last layer the streams are summed, then
RMSNorm and the untied head over this chip's slice of the vocabulary.

- mixer ``K``, KDA (``heads`` x ``head_dim``): ``[q | k | v] = u.w_qkv``,
  each ``silu(sum_k conv_w[k] x[t - (taps - 1) + k])`` (no bias, zeros
  before the sequence); q, k ``x / sqrt(sum x^2 + 1e-6)`` a head; ``beta =
  sigmoid(u.w_beta)``; ``g = gate_lower_bound sigmoid(exp(A_log)_h
  ((u.w_fa).w_fb + dt_bias))`` a key channel; token by token ``S <-
  exp(g_t) S``, ``S <- S + k_t (beta_t (v_t - S^T k_t))^T``, ``o_t = S^T
  q_t / sqrt(head_dim)``; ``y = (RMSNorm_head(o) o_norm * sigmoid((u.w_ga)
  .w_gb)).w_o``.
- mixer ``D``, sparse MLA without rope: ``c_q = RMSNorm(u.wqa)``, ``q =
  c_q.wqb`` (heads x nope_dim); ``c_kv = RMSNorm(u.wkva)``; ``[k_h ; v_h] =
  c_kv.wkvb``; indexer ``q_I = c_q.wqi`` (idx_heads x idx_dim), ``k_I =
  LayerNorm(u.wki)`` (eps 1e-6, ``ik_norm``, ``ik_bias``), both rotated in
  interleaved pairs on their first ``idx_rope_dim`` dimensions (theta
  ``idx_theta``); ``w = u.wiw``; a block's pooled key is the mean of its
  ``idx_pool`` keys; ``I[t, b] = sum_j w_j relu(q_I,j . kbar_b)`` over the
  blocks ``b < t // idx_pool`` (all complete); query t reads the positions
  ``s <= t`` of its own block and of the ``idx_topk / idx_pool - 1``
  blocks of largest I (a tie to the lower block; all while no more);
  ``softmax(q_h . k_h / sqrt(nope_dim))``, ``o_h = sum p v_h``; ``out =
  concat(o).wo``.
- feed-forward ``d``: SwiGLU of ``dense_width``; ``s``: ``sigmoid(b.wr)``
  over ALL ``experts``, the top ``experts_per_token`` by ``s + bias``, gates
  ``s`` over their sum (+1e-20) x ``gate_scale``, the held experts' SwiGLUs
  plus the shared expert's. Every SwiGLU ``silu(min(gate, L)) *
  clip(up, -L, L)`` with ``L = swiglu_limit``.

Weights are made from the seed ONE LAYER AT A TIME (a leaf of experts an
expert at a time, ``_maker``) and every sequence is taken through a layer
before the next is made. Leaves in
``spec["bf16_leaves"]`` hold bfloat16-representable values, as the
configuration stores them.

``precision``: ``"f32"`` is the reference; ``"fp8"`` the CONTROL: both
operands of every matmul rounded to float8_e4m3, per-tensor scaled (never
the router's product, which picks experts, the indexer's scores, which
pick blocks, the mHC coefficients, nor the recurrence and the
convolution, which are elementwise).
"""

from __future__ import annotations

import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.latent_moe_decoder import seed_key
from chipbench.reference.sparse_moe_decoder import (
    _HI, _einsum, _rms_norm, _round_bf16, QUERY_BLOCK)

_NEG_INF = float("-inf")


def _hc_shapes(spec, d):
    n = spec["hc_mult"]
    coef = 2 * n + n * n
    out = {}
    for name in ("hc_attn", "hc_ffn"):
        out.update({f"{name}_phi": (n * d, coef), f"{name}_scale": (3,),
                    f"{name}_bias": (coef,)})
    return out


def layer_leaf_shapes(spec, i):
    d = spec["hidden"]
    out = {"ln1": (d,), "ln2": (d,), **_hc_shapes(spec, d)}
    if spec["mixers"][i] == "K":
        h, dk, r = spec["heads"], spec["head_dim"], spec["gate_rank"]
        w = h * dk
        out.update(w_qkv=(d, 3 * w), conv_w=(spec["conv_taps"], 3 * w),
                   w_beta=(d, h), A_log=(h,), w_fa=(d, r), w_fb=(r, w),
                   dt_bias=(w,), o_norm=(dk,), w_ga=(d, r), w_gb=(r, w),
                   w_o=(w, d))
    else:
        h, nope, vd = spec["mla_heads"], spec["nope_dim"], spec["v_dim"]
        qr, kr = spec["q_rank"], spec["kv_rank"]
        hi, di = spec["idx_heads"], spec["idx_dim"]
        out.update(wqa=(d, qr), q_norm=(qr,), wqb=(qr, h * nope),
                   wkva=(d, kr), kv_norm=(kr,), wkvb=(kr, h * (nope + vd)),
                   wo=(h * vd, d), wqi=(qr, hi * di), wki=(d, di),
                   ik_norm=(di,), ik_bias=(di,), wiw=(d, hi))
    if spec["mlps"][i] == "d":
        wf = spec["dense_width"]
        out.update(wf_gate_up=(d, 2 * wf), wf_down=(wf, d))
    else:
        held, w, ws = spec["held"][1], spec["expert_width"], \
            spec["shared_width"]
        out.update(wr=(d, spec["experts"]), bias=(spec["experts"],),
                   w_gate_up=(held, d, 2 * w), w_down=(held, w, d),
                   ws_gate_up=(d, 2 * ws), ws_down=(ws, d))
    return out


def top_leaf_shapes(spec):
    d, v = spec["hidden"], spec["vocab"]
    return {"embed": (v, d), "final_norm": (d,), "lm_head": (d, v)}


def _rule(name, shape):
    """How a leaf is drawn from its normal: its kind of initialisation."""
    if name == "bias":
        return "selection_bias"
    if name == "A_log":
        return "log_uniform"
    if name in ("dt_bias", "hc_attn_bias", "hc_ffn_bias"):
        return "normal"
    if name == "ik_bias":
        return "small"
    if len(shape) == 1:
        return "gain"
    return "normal" if name == "embed" else "fan_in"


@functools.lru_cache(maxsize=None)
def _maker(shape, rule, bf16, stored, bias_std, a_range):
    """One leaf from its key. A leaf of experts ``(count, ...)`` is drawn
    an expert at a time (``fold_in(key, e)``), inside one program whose
    output is the leaf alone: the served model's weights are loaded a
    leaf at a time into a chip whose pools and other weights leave 1.1 GB
    free, where a whole layer's leaves in one program wanted 2.25 GB (my
    chip run, PR 39)."""

    def draw(key, shape):
        x = jax.random.normal(key, shape, jnp.float32)
        if rule == "selection_bias":
            x = bias_std * x
        elif rule == "log_uniform":
            x = jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                           a_range[0], a_range[1]))
        elif rule == "small":
            x = 0.02 * x
        elif rule == "gain":
            x = 1.0 + 0.02 * x
        elif rule == "fan_in":                   # 1/sqrt(fan_in)
            x = x * shape[-2] ** -0.5
        if bf16:
            x = x.astype(jnp.bfloat16) if stored else _round_bf16(x)
        return x

    @jax.jit
    def make(key):
        if len(shape) == 3:
            return jax.lax.map(
                lambda e: draw(jax.random.fold_in(key, e), shape[1:]),
                jnp.arange(shape[0]))
        return draw(key, shape)

    return make


def _leaf_makers(spec, shapes, key, prefix, stored):
    """``[(name, make)]``: each leaf's weights when ``make()`` is called
    (the j-th leaf by name from ``fold_in(key, j)``)."""
    out = []
    for j, name in enumerate(sorted(shapes)):
        shape = tuple(shapes[name])
        make = _maker(shape, _rule(name, shape),
                      prefix + name in spec.get("bf16_leaves", ()), stored,
                      float(spec["bias_std"]), tuple(spec["a_range"]))
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
    """The embedding, the final norm and the untied head."""
    return _leaf_makers(spec, top_leaf_shapes(spec),
                        jax.random.fold_in(seed_key(seed), 0), "", stored)


def init_layer(spec, seed, i, stored=False):
    return {name: make() for name, make in
            layer_leaf_makers(spec, seed, i, stored)}


def init_top(spec, seed, stored=False):
    return {name: make() for name, make in
            top_leaf_makers(spec, seed, stored)}


# -- the mathematics ---------------------------------------------------------

def sinkhorn(m, iters, eps):
    """``m (..., n, n)`` positive: ``iters`` times a row then a column
    normalisation."""
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def hyper(x, lp, name, spec, fn):
    """``x (S, n, d)`` streams around the sublayer ``fn``."""
    s_len, n, d = x.shape
    flat = x.reshape(s_len, n * d)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + spec["rms_eps"])
    proj = jnp.dot(flat, lp[name + "_phi"], precision=_HI)
    scale, bias = lp[name + "_scale"], lp[name + "_bias"]
    pre = jax.nn.sigmoid(scale[0] * proj[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(scale[1] * proj[:, n:2 * n] + bias[n:2 * n])
    res = sinkhorn(jnp.exp(scale[2] * proj[:, 2 * n:] + bias[2 * n:]
                           ).reshape(s_len, n, n),
                   spec["hc_iters"], spec["hc_eps"])
    f = fn(jnp.einsum("sn,snd->sd", pre, x, precision=_HI))
    return (jnp.einsum("sij,sjd->sid", res, x, precision=_HI)
            + post[:, :, None] * f[:, None, :])


def conv(x, w):
    """The causal depthwise convolution without bias: ``x (S, C)``."""
    taps, s_len = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(w[k] * padded[k:k + s_len] for k in range(taps))


def delta_rule(q, k, v, g, beta):
    """Token by token: ``q, k, g (S, H, K)``, ``v (S, H, V)``, ``beta (S,
    H)`` -> ``o (S, H, V)`` = ``S_t^T q_t`` (unscaled)."""

    def step(s, t):
        q_t, k_t, v_t, g_t, b_t = t
        s = jnp.exp(g_t)[:, :, None] * s
        ks = jnp.einsum("hk,hkv->hv", k_t, s, precision=_HI)
        s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - ks))[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=_HI)

    s0 = jnp.zeros(q.shape[1:] + v.shape[-1:], jnp.float32)
    _, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kda(u, lp, spec, precision="f32"):
    """``u (S, d)`` normed -> the mixer's output ``(S, d)``."""
    s_len = u.shape[0]
    h, dk = spec["heads"], spec["head_dim"]
    w = h * dk
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)
    qkv = jax.nn.silu(conv(mm(u, lp["w_qkv"]), lp["conv_w"]))
    q, k, v = (qkv[:, j * w:(j + 1) * w].reshape(s_len, h, dk)
               for j in range(3))
    beta = jax.nn.sigmoid(mm(u, lp["w_beta"]))
    f = (mm(mm(u, lp["w_fa"]), lp["w_fb"]) + lp["dt_bias"]).reshape(
        s_len, h, dk)
    g = spec["gate_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(lp["A_log"])[:, None] * f)
    o = delta_rule(_l2norm(q), _l2norm(k), v, g, beta) * dk ** -0.5
    o = _rms_norm(o, lp["o_norm"], spec["rms_eps"])
    gate = jax.nn.sigmoid(mm(mm(u, lp["w_ga"]), lp["w_gb"]))
    return mm(o.reshape(s_len, w) * gate, lp["w_o"])


def _rope_interleaved(x, positions, spec):
    """``x (S, H, D)``: the pairs ``(x[2i], x[2i+1])`` of the first
    ``idx_rope_dim`` dimensions rotated by ``pos theta^(-2i/idx_rope_dim)``,
    the rest as it is."""
    dr = spec["idx_rope_dim"]
    inv_freq = spec["idx_theta"] ** (
        -jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dr:2], x[..., 1:dr:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.reshape(x.shape[:-1] + (dr,)),
                            x[..., dr:]], -1)


def block_choice(scores, tail, spec):
    """``scores (Q, B)`` of queries against every block, ``tail (Q,)`` each
    query's own block: ``(Q, B)`` bool, the ``idx_topk / idx_pool - 1``
    blocks ``< tail`` of largest score (a tie to the lower block)."""
    n_blocks = scores.shape[1]
    kb = min(spec["idx_topk"] // spec["idx_pool"] - 1, n_blocks)
    cand = jnp.arange(n_blocks)[None, :] < tail[:, None]
    masked = jnp.where(cand, scores, _NEG_INF)
    _, top = jax.lax.top_k(masked, kb)
    rows = jnp.arange(scores.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[rows, top].set(True)
    return chosen & cand


def dsa(u, lp, spec, precision="f32"):
    """``u (S, d)`` -> concat(o) ``(S, heads * v_dim)``; S is a multiple of
    QUERY_BLOCK and of ``idx_pool``."""
    s_len = u.shape[0]
    h, nope, vd = spec["mla_heads"], spec["nope_dim"], spec["v_dim"]
    hi, di, pool = spec["idx_heads"], spec["idx_dim"], spec["idx_pool"]
    eps = spec["rms_eps"]
    pos = jnp.arange(s_len)
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)
    c_q = _rms_norm(mm(u, lp["wqa"]), lp["q_norm"], eps)
    q = mm(c_q, lp["wqb"]).reshape(s_len, h, nope)
    c_kv = _rms_norm(mm(u, lp["wkva"]), lp["kv_norm"], eps)
    up = mm(c_kv, lp["wkvb"]).reshape(s_len, h, nope + vd)
    k, v = up[..., :nope], up[..., nope:]
    # the indexer: never rounded for the control, it picks blocks
    q_i = _rope_interleaved(jnp.dot(c_q, lp["wqi"], precision=_HI).reshape(
        s_len, hi, di), pos, spec)
    k_i = jnp.dot(u, lp["wki"], precision=_HI)
    mu = k_i.mean(-1, keepdims=True)
    var = ((k_i - mu) ** 2).mean(-1, keepdims=True)
    k_i = (k_i - mu) * jax.lax.rsqrt(var + 1e-6) * lp["ik_norm"] \
        + lp["ik_bias"]
    k_i = _rope_interleaved(k_i[:, None], pos, spec)[:, 0]
    k_pool = k_i.reshape(s_len // pool, pool, di).mean(1)
    w_i = jnp.dot(u, lp["wiw"], precision=_HI)

    def block(start):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=start, slice_size=QUERY_BLOCK)
        t = start + jnp.arange(QUERY_BLOCK)
        scores = jnp.einsum("qj,qjb->qb", sl(w_i), jax.nn.relu(jnp.einsum(
            "qjd,bd->qjb", sl(q_i), k_pool, precision=_HI)), precision=_HI)
        chosen = block_choice(scores, t // pool, spec)
        seen = (pos[None, :] <= t[:, None]) & (
            (pos[None, :] // pool == (t // pool)[:, None])
            | jnp.repeat(chosen, pool, axis=1))
        logits = _einsum("qhd,shd->hqs", sl(q), k, precision) * nope ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], logits, _NEG_INF), axis=-1)
        return _einsum("hqs,shd->qhd", p, v, precision).reshape(
            QUERY_BLOCK, h * vd)

    o = jax.lax.map(block, jnp.arange(0, s_len, QUERY_BLOCK))
    return mm(o.reshape(s_len, h * vd), lp["wo"])


def _swiglu(b, w_gate_up, w_down, spec, precision):
    width, lim = w_down.shape[0], spec["swiglu_limit"]
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)
    gate = jnp.minimum(mm(b, w_gate_up[:, :width]), lim)
    up = jnp.clip(mm(b, w_gate_up[:, width:]), -lim, lim)
    return mm(jax.nn.silu(gate) * up, w_down)


def route(b, wr, bias, spec):
    """Gates ``(S, experts)``: ``s_e / (sum_E s + 1e-20) * gate_scale`` on
    the token's top-k experts BY ``s + bias``, 0 elsewhere. Never rounded
    for the control: it picks experts."""
    s = jax.nn.sigmoid(jnp.dot(b, wr, precision=_HI))
    _, top_e = jax.lax.top_k(s + bias, spec["experts_per_token"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if spec["norm_topk"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    rows = jnp.arange(b.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, top_e].set(top_s * spec["gate_scale"])


def routed_part(b, lp, spec, precision="f32"):
    """``sum_{e in E, e held} g_e E_e(b)``: this chip's part of the routed
    sum, a loop over the experts it holds."""
    first, count = spec["held"]
    gates = route(b, lp["wr"], lp["bias"], spec)

    def expert(y, e):
        out = _swiglu(b, lp["w_gate_up"][e], lp["w_down"][e], spec,
                      precision)
        return y + gates[:, first + e, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(b), jnp.arange(count))
    return y


def shared_part(b, lp, spec, precision="f32"):
    """What every chip that shares the layer computes alike."""
    return _swiglu(b, lp["ws_gate_up"], lp["ws_down"], spec, precision)


@functools.partial(jax.jit, static_argnames=("spec_json", "precision",
                                             "kind"))
def _layer(x, lp, spec_json, precision, kind):
    spec = json.loads(spec_json)
    eps = spec["rms_eps"]
    mixer, mlp = kind

    def attend(h):
        u = _rms_norm(h, lp["ln1"], eps)
        if mixer == "K":
            return kda(u, lp, spec, precision)
        return dsa(u, lp, spec, precision)

    def ffn(h):
        b = _rms_norm(h, lp["ln2"], eps)
        if mlp == "d":
            return _swiglu(b, lp["wf_gate_up"], lp["wf_down"], spec,
                           precision)
        return (routed_part(b, lp, spec, precision)
                + shared_part(b, lp, spec, precision))

    x = hyper(x, lp, "hc_attn", spec, attend)
    return hyper(x, lp, "hc_ffn", spec, ffn)


@functools.partial(jax.jit, static_argnames=("spec_json", "precision"))
def _head(x, positions, top, spec_json, precision):
    spec = json.loads(spec_json)
    h = _rms_norm(x[positions].sum(1), top["final_norm"], spec["rms_eps"])
    return _einsum("sd,dv->sv", h, top["lm_head"], precision)


def logits_at(spec, seed, seqs, positions, precision="f32", timings=None):
    """Full forward over each of ``seqs`` (1-D id arrays), a layer at a
    time over all of them; returns, per sequence, the logits ``(len(p),
    vocab)`` at its ``positions`` p. ``timings``: a dict that gets the
    seconds spent making weights and in the layers (each synced)."""
    spec_json = json.dumps(spec, sort_keys=True)
    spent = {"weights_s": 0.0, "layers_s": 0.0, "head_s": 0.0}
    n = spec["hc_mult"]
    unit = math.lcm(QUERY_BLOCK, spec["idx_pool"])

    def timed(key, fn, *args, **kw):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kw))
        spent[key] += time.perf_counter() - t
        return out

    with jax.default_matmul_precision("highest"):
        top = timed("weights_s", init_top, spec, seed)
        hidden = []
        # every sequence padded behind its last token (never seen) to one
        # length: one program a kind of layer, not one a sequence
        length = -(-max(len(ids) for ids in seqs) // unit) * unit
        for ids in seqs:
            ids = np.pad(np.asarray(ids, np.int32), (0, length - len(ids)))
            e = top["embed"][jnp.asarray(ids)]
            hidden.append(jnp.repeat(e[:, None], n, axis=1))
        for i, kind in enumerate(zip(spec["mixers"], spec["mlps"])):
            lp = timed("weights_s", init_layer, spec, seed, i)
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
    """What ``latent_moe_decoder.served_token_gaps`` returns, for this
    model: per request a dict of arrays over the positions that emitted a
    served token — ``gap`` (best logit minus the served token's),
    ``logprob`` (of the served token), ``margin`` (best minus second),
    ``second`` (the second-best token); with ``control`` (a precision
    name) also ``control_gap`` and ``control_logprob``."""
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
