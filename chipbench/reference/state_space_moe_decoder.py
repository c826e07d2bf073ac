"""The plain reference of the hybrid state-space routed-FFN decoder, as ONE
CHIP'S SHARE of a deployment that divides every routed layer's experts over
several chips.

float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
written from the layer equations (PERF.md section 4; ISSUE 35), independent
of ``simple_tensorflow_tpu``: it imports nothing of the program. A full
forward pass over prompt + served tokens: no cache, no chunks, no slots, no
kernels, no batching. The recurrence is a ``lax.scan`` over TOKENS, the
convolution a padded sum of shifted copies, attention a plain softmax, the
experts a plain loop over the ones held. Helpers the other references
already have are imported from them.

``pattern`` names each layer's kind; every layer is ``x <- x +
mixer(RMSNorm(x; rms_eps))``:

- ``M``, Mamba-2. ``d_inner = mamba_heads x mamba_head_dim``, ``G =
  groups``, ``N = state``, conv width ``C = d_inner + 2 G N``. ``[z (d_inner)
  | xBC (C) | dt (mamba_heads)] = u.w_in``; ``xBC[t] <- silu(conv_b + sum_k
  conv_w[k] xBC[t - (taps - 1) + k])``, zeros before the sequence; ``[x | B |
  C] = xBC``, ``mamba_heads / G`` heads a group; ``dt <- softplus(dt +
  dt_bias)``; ``A = -exp(A_log)``; per head ``h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``; ``y <- RMSNorm_groups(y *
  silu(z)) * gnorm`` over G groups; ``out = y.w_out``.
- ``E``, routed FFN. ``s = sigmoid(u.wr)`` over ALL ``experts``; E = the
  ``experts_per_token`` experts of largest ``s + bias`` (a tie to the lower
  expert); ``g_e = s_e / (sum_E s + 1e-20) * gate_scale``; ``out = sum_{e in
  E, e held} g_e relu(u.w_up_e)^2.w_down_e + relu(u.ws_up)^2.ws_down``.
  ``held = [first, count]``: the experts this chip holds; what the absent
  experts would add is left out, as in the program.
- ``*``, attention. ``heads`` query / ``kv_heads`` key-value heads x
  ``head_dim``, query head h with key-value head ``h // (heads /
  kv_heads)``, ``softmax(q.k / sqrt(head_dim))`` over s <= t, no position
  embedding; ``out = concat(o).wo``.
- after the last layer RMSNorm, then the untied head over this chip's slice
  of the vocabulary.

Weights are made from the seed ONE LAYER AT A TIME and every sequence is
taken through a layer before the next is made. Leaves in
``spec["bf16_leaves"]`` hold bfloat16-representable values, as the
configuration stores them. ``A_log = log U[a_range]``, ``dt_bias`` the
inverse softplus of a log-uniform draw from ``dt_range``, ``D = 1``.

``precision``: ``"f32"`` is the reference; ``"fp8"`` the CONTROL: both
operands of every matmul rounded to float8_e4m3, per-tensor scaled (never
the router's product, which picks experts, nor the recurrence and the
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


def layer_leaf_shapes(spec, i):
    d = spec["hidden"]
    kind = spec["pattern"][i]
    if kind == "M":
        h, p = spec["mamba_heads"], spec["mamba_head_dim"]
        di = h * p
        conv = di + 2 * spec["groups"] * spec["state"]
        return {"norm": (d,), "w_in": (d, di + conv + h),
                "conv_w": (spec["conv_taps"], conv), "conv_b": (conv,),
                "dt_bias": (h,), "A_log": (h,), "D": (h,), "gnorm": (di,),
                "w_out": (di, d)}
    if kind == "E":
        held, w, ws = spec["held"][1], spec["expert_width"], \
            spec["shared_width"]
        return {"norm": (d,), "wr": (d, spec["experts"]),
                "bias": (spec["experts"],), "w_up": (held, d, w),
                "w_down": (held, w, d), "ws_up": (d, ws), "ws_down": (ws, d)}
    if kind == "*":
        h, kv, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
        return {"norm": (d,), "wq": (d, h * hd), "wk": (d, kv * hd),
                "wv": (d, kv * hd), "wo": (h * hd, d)}
    raise ValueError(f"unknown layer kind {kind!r}")


def top_leaf_shapes(spec):
    d, v = spec["hidden"], spec["vocab"]
    return {"embed": (v, d), "final_norm": (d,), "lm_head": (d, v)}


@functools.lru_cache(maxsize=None)
def _maker(shapes_json, bf16, stored, bias_std, a_range, dt_range):
    shapes = {k: tuple(v) for k, v in json.loads(shapes_json)}

    @jax.jit
    def make(key):
        out = {}
        for j, (name, shape) in enumerate(sorted(shapes.items())):
            sub = jax.random.fold_in(key, j)
            x = jax.random.normal(sub, shape, jnp.float32)
            if name == "bias":                       # the selection bias
                x = bias_std * x
            elif name == "A_log":
                x = jnp.log(jax.random.uniform(
                    sub, shape, jnp.float32, a_range[0], a_range[1]))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    sub, shape, jnp.float32, math.log(dt_range[0]),
                    math.log(dt_range[1])))
                x = dt + jnp.log(-jnp.expm1(-dt))    # softplus^-1(dt)
            elif name == "D":
                x = jnp.ones(shape, jnp.float32)
            elif name == "conv_b":
                x = 0.1 * x
            elif len(shape) == 1:                    # a norm's gain
                x = 1.0 + 0.02 * x
            elif name != "embed":                    # a matrix: 1/sqrt(fan_in)
                x = x * shape[-2] ** -0.5
            if name in bf16:
                x = x.astype(jnp.bfloat16) if stored else _round_bf16(x)
            out[name] = x
        return out

    return make


def _make(spec, shapes, key, prefix, stored):
    bf16 = frozenset(name for name in shapes
                     if prefix + name in spec.get("bf16_leaves", ()))
    return _maker(json.dumps(sorted(shapes.items())), bf16, stored,
                  float(spec["bias_std"]), tuple(spec["a_range"]),
                  tuple(spec["dt_range"]))(key)


def init_layer(spec, seed, i, stored=False):
    """Layer ``i``'s weights from the seed, on the default device: float32
    arrays, or with ``stored`` the ``bf16_leaves`` as bfloat16 arrays of
    the same values."""
    return _make(spec, layer_leaf_shapes(spec, i),
                 jax.random.fold_in(seed_key(seed), i + 1), "layers.", stored)


def init_top(spec, seed, stored=False):
    """The embedding, the final norm and the untied head."""
    return _make(spec, top_leaf_shapes(spec),
                 jax.random.fold_in(seed_key(seed), 0), "", stored)


# -- the mathematics ---------------------------------------------------------

def conv(xbc, lp):
    """The causal depthwise convolution as a padded sum of shifted copies:
    ``xbc (S, C)``."""
    taps, s_len = lp["conv_w"].shape[0], xbc.shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    return lp["conv_b"] + sum(lp["conv_w"][k] * padded[k:k + s_len]
                              for k in range(taps))


def recurrence(x, dt, a, bm, cm, skip):
    """Token by token: ``x (S, H, P)``, ``dt (S, H)``, ``a, skip (H,)``,
    ``bm, cm (S, H, N)`` (each head its group's) -> ``(y (S, H, P)``, the
    last state ``(H, P, N))``."""

    def step(h, t):
        x_t, dt_t, b_t, c_t = t
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], -1) + skip[:, None] * x_t

    h0 = jnp.zeros(x.shape[1:] + bm.shape[-1:], jnp.float32)
    h, y = jax.lax.scan(step, h0, (x, dt, bm, cm))
    return y, h


def mamba(u, lp, spec, precision="f32"):
    """``u (S, d)`` normed hidden states -> the mixer's output ``(S, d)``."""
    s_len = u.shape[0]
    h, p = spec["mamba_heads"], spec["mamba_head_dim"]
    g, n = spec["groups"], spec["state"]
    di = h * p
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)
    zxbcdt = mm(u, lp["w_in"])
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + di + 2 * g * n],
                  zxbcdt[:, -h:])
    xbc = jax.nn.silu(conv(xbc, lp))
    x = xbc[:, :di].reshape(s_len, h, p)
    bm = jnp.repeat(xbc[:, di:di + g * n].reshape(s_len, g, n), h // g, 1)
    cm = jnp.repeat(xbc[:, di + g * n:].reshape(s_len, g, n), h // g, 1)
    y, _ = recurrence(x, jax.nn.softplus(dt + lp["dt_bias"]),
                      -jnp.exp(lp["A_log"]), bm, cm, lp["D"])
    gated = (y.reshape(s_len, di) * jax.nn.silu(z)).reshape(
        s_len, g, di // g)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + spec["rms_eps"])
    return mm(gated.reshape(s_len, di) * lp["gnorm"], lp["w_out"])


def attention(u, lp, spec, precision="f32"):
    """``u (S, d)`` -> ``(S, d)``; S is a multiple of QUERY_BLOCK."""
    s_len = u.shape[0]
    h, kv, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
    pos = jnp.arange(s_len)
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)
    q = mm(u, lp["wq"]).reshape(s_len, kv, h // kv, hd)
    k = mm(u, lp["wk"]).reshape(s_len, kv, hd)
    v = mm(u, lp["wv"]).reshape(s_len, kv, hd)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK)
        seen = pos[None, :] <= (start + jnp.arange(QUERY_BLOCK))[:, None]
        logits = _einsum("qgrd,sgd->grqs", qb, k, precision) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(seen[None, None], logits, _NEG_INF),
                              axis=-1)
        return _einsum("grqs,sgd->qgrd", prob, v, precision).reshape(
            QUERY_BLOCK, h * hd)

    o = jax.lax.map(block, jnp.arange(0, s_len, QUERY_BLOCK))
    return mm(o.reshape(s_len, h * hd), lp["wo"])


def _relu2(b, w_up, w_down, precision):
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)
    return mm(jnp.square(jax.nn.relu(mm(b, w_up))), w_down)


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
        out = _relu2(b, lp["w_up"][e], lp["w_down"][e], precision)
        return y + gates[:, first + e, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(b), jnp.arange(count))
    return y


def shared_part(b, lp, precision="f32"):
    """What every chip that shares the layer computes alike."""
    return _relu2(b, lp["ws_up"], lp["ws_down"], precision)


@functools.partial(jax.jit, static_argnames=("spec_json", "precision",
                                             "kind"))
def _layer(x, lp, spec_json, precision, kind):
    spec = json.loads(spec_json)
    u = _rms_norm(x, lp["norm"], spec["rms_eps"])
    if kind == "M":
        return x + mamba(u, lp, spec, precision)
    if kind == "*":
        return x + attention(u, lp, spec, precision)
    return (x + routed_part(u, lp, spec, precision)
            + shared_part(u, lp, precision))


@functools.partial(jax.jit, static_argnames=("spec_json", "precision"))
def _head(x, positions, top, spec_json, precision):
    spec = json.loads(spec_json)
    h = _rms_norm(x[positions], top["final_norm"], spec["rms_eps"])
    return _einsum("sd,dv->sv", h, top["lm_head"], precision)


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
        hidden = []
        for ids in seqs:
            pad = -len(ids) % QUERY_BLOCK           # behind the last token:
            ids = np.pad(np.asarray(ids, np.int32), (0, pad))   # never seen
            hidden.append(top["embed"][jnp.asarray(ids)])
        for i, kind in enumerate(spec["pattern"]):
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
