"""The plain reference of the latent-attention routed-FFN decoder, as ONE
CHIP'S SHARE of a deployment that divides every layer over several chips.

float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
written from the layer equations (PERF.md section 4; ISSUE 33), independent
of ``simple_tensorflow_tpu``: it imports nothing of the program. A full
forward pass over prompt + served tokens: no cache, no kernels, no batching;
attention in the PLAIN form (keys and values up-projected per head from the
latent), the experts a plain loop over the ones held. Helpers that the
sparse model's reference already has are imported from it.

One layer (``x`` a token's hidden state at position t, s <= t a position):

- ``a = RMSNorm(x)``. Query: ``c_q = RMSNorm(a.wqa)`` (``q_rank``; eps
  ``latent_eps``), ``q = c_q.wqb`` -> heads x (``qk_nope_dim`` +
  ``qk_rope_dim``); RoPE on the rope part. Latent: ``[c_kv ; k_r] =
  a.wkva`` (``kv_rank`` + ``qk_rope_dim``); ``c_kv <- RMSNorm(c_kv)`` (eps
  ``latent_eps``); ``k_r <- RoPE(k_r)``, ONE rope key for all heads.
- ``[k_nope_h ; v_h] = c_kv.wkvb`` -> heads x (``qk_nope_dim`` + ``v_dim``);
  ``score_h[t, s] = sigma (q_nope_h . k_nope_h[s] + q_rope_h . k_r[s])``;
  float32 softmax over s <= t; ``o_h = sum p v_h[s]``; ``x +=
  concat_h(o_h).wo``.
- ``sigma = (qk_nope_dim + qk_rope_dim)^-0.5 m^2``, ``m = 0.1 mscale_all_dim
  ln(factor) + 1``. RoPE is rotate-half over the rope dimensions with YaRN's
  frequencies: ``f_i = theta^(-2i/D)``, ``dim(n) = D ln(original_len / (2 pi
  n)) / (2 ln theta)``, ``low = floor(dim(beta_fast))``, ``high =
  ceil(dim(beta_slow))``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
  ``inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i``; cos and sin
  scaled by ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
  mscale_all_dim)``.
- ``b = RMSNorm(x)``. Layers ``< dense_layers``: ``x += wf_down(silu(wf_gate
  b) * wf_up b)``. The others: ``s = sigmoid(b.wr)`` over ALL ``experts``;
  E = the ``experts_per_token`` experts of largest ``s + bias`` (a tie to the
  lower expert); ``g_e = s_e / (sum_E s + 1e-20) * gate_scale`` — the bias
  chooses, it does not weigh; ``x += sum_{e in E, e held} g_e E_e(b) +
  S(b)``, each ``E_e`` and the shared expert ``S`` a SwiGLU. ``held =
  [first, count]``: the experts this chip holds; what the absent experts
  would add is left out, as in the program (model-configs guide, section 4).
- after the last layer RMSNorm, then the untied head over this chip's slice
  of the vocabulary (``vocab`` rows: a smaller vocabulary).

Weights are made from the seed ONE LAYER AT A TIME and every sequence is
taken through a layer before the next is made; queries go in blocks of
``QUERY_BLOCK``. Leaves in ``spec["bf16_leaves"]`` hold
bfloat16-representable values, as the configuration stores them.

``precision``: ``"f32"`` is the reference; ``"fp8"`` the CONTROL: both
operands of every matmul rounded to float8_e4m3, per-tensor scaled (the
router's product is never rounded: it picks experts).
"""

from __future__ import annotations

import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.sparse_moe_decoder import (
    _HI, _einsum, _rms_norm, _round_bf16, QUERY_BLOCK)

_NEG_INF = float("-inf")


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62, of the ``rbg``
    implementation, the chip's own bit generator (a leaf of 352 M values
    in 7 ms where threefry takes 12; my chip run, PR 33). The program's
    weights and the reference's are made by this one function in one
    process, so they agree whatever the generator; the cell's limits were
    read with this one."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed >> 31, impl="rbg"),
                              seed & 0x7FFFFFFF)


def is_dense(spec, i):
    return i < spec["dense_layers"]


def layer_leaf_shapes(spec, i):
    d, h = spec["hidden"], spec["heads"]
    nope, rope, v = spec["qk_nope_dim"], spec["qk_rope_dim"], spec["v_dim"]
    qr, kr = spec["q_rank"], spec["kv_rank"]
    out = {"ln1": (d,), "wqa": (d, qr), "q_norm": (qr,),
           "wqb": (qr, h * (nope + rope)), "wkva": (d, kr + rope),
           "kv_norm": (kr,), "wkvb": (kr, h * (nope + v)),
           "wo": (h * v, d), "ln2": (d,)}
    if is_dense(spec, i):
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


@functools.lru_cache(maxsize=None)
def _maker(shapes_json, bf16, stored, bias_std):
    shapes = {k: tuple(v) for k, v in json.loads(shapes_json)}

    @jax.jit
    def make(key):
        out = {}
        for j, (name, shape) in enumerate(sorted(shapes.items())):
            x = jax.random.normal(jax.random.fold_in(key, j), shape,
                                  jnp.float32)
            if name == "bias":                       # the selection bias
                x = bias_std * x
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
                  float(spec["bias_std"]))(key)


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

def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(spec):
    """``sigma``: ``(qk_nope_dim + qk_rope_dim)^-0.5 m^2``."""
    y = spec["yarn"]
    m = yarn_mscale(y["factor"], y["mscale_all_dim"])
    return (spec["qk_nope_dim"] + spec["qk_rope_dim"]) ** -0.5 * m * m


def yarn_inv_freq(spec):
    """``(inv_freq (D/2,), amplitude)`` of the rope slice."""
    y, dim, theta = spec["yarn"], spec["qk_rope_dim"], spec["rope_theta"]

    def turns_dim(n):
        return dim * math.log(y["original_len"] / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_dim(y["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(y["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * i / dim)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = freq * (1.0 - ramp) + freq / y["factor"] * ramp
    amplitude = (yarn_mscale(y["factor"], y["mscale"])
                 / yarn_mscale(y["factor"], y["mscale_all_dim"]))
    return jnp.asarray(inv_freq, jnp.float32), amplitude


def _rope(x, positions, spec):
    """``x (S, H, D)``, rotate-half over all of ``D``."""
    d = x.shape[-1]
    inv_freq, amplitude = yarn_inv_freq(spec)
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, lp, spec, precision):
    """``a (S, d)`` normed hidden states -> concat(o) ``(S, heads *
    v_dim)``; S is a multiple of QUERY_BLOCK."""
    s_len = a.shape[0]
    h, nope, rope = spec["heads"], spec["qk_nope_dim"], spec["qk_rope_dim"]
    v_dim, kr = spec["v_dim"], spec["kv_rank"]
    eps = spec["latent_eps"]
    pos = jnp.arange(s_len)
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)

    c_q = _rms_norm(mm(a, lp["wqa"]), lp["q_norm"], eps)
    q = mm(c_q, lp["wqb"]).reshape(s_len, h, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, spec)
    kv = mm(a, lp["wkva"])
    c_kv = _rms_norm(kv[:, :kr], lp["kv_norm"], eps)
    k_r = _rope(kv[:, None, kr:], pos, spec)[:, 0]        # one key, all heads
    up = mm(c_kv, lp["wkvb"]).reshape(s_len, h, nope + v_dim)
    k_nope, v = up[..., :nope], up[..., nope:]
    sigma = softmax_scale(spec)

    def block(start):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=start, slice_size=QUERY_BLOCK)
        t = start + jnp.arange(QUERY_BLOCK)
        seen = pos[None, :] <= t[:, None]
        logits = (_einsum("qhd,shd->hqs", sl(q_nope), k_nope, precision)
                  + _einsum("qhd,sd->hqs", sl(q_rope), k_r, precision)
                  ) * sigma
        p = jax.nn.softmax(jnp.where(seen[None], logits, _NEG_INF), axis=-1)
        return _einsum("hqs,shd->qhd", p, v, precision).reshape(
            QUERY_BLOCK, h * v_dim)

    starts = jnp.arange(0, s_len, QUERY_BLOCK)
    return jax.lax.map(block, starts).reshape(s_len, h * v_dim)


def _swiglu(b, w_gate_up, w_down, precision):
    width = w_down.shape[0]
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)
    return mm(jax.nn.silu(mm(b, w_gate_up[:, :width]))
              * mm(b, w_gate_up[:, width:]), w_down)


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
        out = _swiglu(b, lp["w_gate_up"][e], lp["w_down"][e], precision)
        return y + gates[:, first + e, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(b), jnp.arange(count))
    return y


def shared_part(b, lp, precision="f32"):
    """``S(b)``: what every chip that shares the layer computes alike."""
    return _swiglu(b, lp["ws_gate_up"], lp["ws_down"], precision)


@functools.partial(jax.jit, static_argnames=("spec_json", "precision",
                                             "dense"))
def _layer(x, lp, spec_json, precision, dense):
    spec = json.loads(spec_json)
    eps = spec["rms_eps"]
    o = _attention(_rms_norm(x, lp["ln1"], eps), lp, spec, precision)
    x = x + _einsum("sd,de->se", o, lp["wo"], precision)
    b = _rms_norm(x, lp["ln2"], eps)
    if dense:
        return x + _swiglu(b, lp["wf_gate_up"], lp["wf_down"], precision)
    return (x + routed_part(b, lp, spec, precision)
            + shared_part(b, lp, precision))


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
        for i in range(spec["layers"]):
            lp = timed("weights_s", init_layer, spec, seed, i)
            hidden = [timed("layers_s", _layer, x, lp, spec_json, precision,
                            dense=is_dense(spec, i)) for x in hidden]
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
    """What ``sparse_moe_decoder.served_token_gaps`` returns, for this
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
