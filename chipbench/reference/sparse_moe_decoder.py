"""The plain reference of the sparse-attention routed-FFN decoder.

float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
written from the layer equations (PERF.md section 4; ISSUE 27), independent
of ``simple_tensorflow_tpu``: it imports nothing of the program and is never
handed the program's selection or routing. A full forward pass over prompt +
served tokens: no cache, no kernels, no batching; the causal AND top-k mask
is dense, the experts are a plain loop over all of them.

One layer (``x`` a token's hidden state at position t, s <= t a position):

- ``a = RMSNorm(x)``; ``q = a.wq`` (heads x head_dim), ``k = a.wk``, ``v =
  a.wv`` (kv_heads x head_dim); RMSNorm per head on q and k; RoPE (rotate
  half, theta) on q and k.
- ``qI = a.wiq`` (indexer_heads x indexer_dim), ``kI = a.wik`` (one head),
  ``w = a.wiw * (indexer_heads * indexer_dim)^-0.5``; RoPE on qI, kI;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; ``S_t`` = the ``topk``
  positions s <= t of largest I (all while t < topk; a tie goes to the
  lower position).
- head h attends ``S_t`` with KV head ``h // (heads / kv_heads)``, scale
  ``head_dim^-0.5``; ``x += concat(o).wo``.
- ``b = RMSNorm(x)``; ``p = softmax(b.wr)``; E = top-k experts of p (a tie
  to the lower expert); ``g = p_E / sum p_E``; ``x += sum_E g_e
  wd_e(silu(wg_e b) * wu_e b)``. ``w_gate_up`` holds ``[wg | wu]``.
- after the last layer RMSNorm, then the untied head.

The 6 layers' float32 weights are 15 GB at the published widths, so weights
are made from the seed ONE LAYER AT A TIME (:func:`init_layer`) and every
sequence is taken through a layer before the next layer is made. Queries are
processed in blocks of ``QUERY_BLOCK`` so that a 33k-token sequence's dense
mask fits. Leaves in ``spec["bf16_leaves"]`` hold bfloat16-representable
values, as the configuration stores them.

``precision``: ``"f32"`` is the reference; ``"fp8"`` the CONTROL: both
operands of every matmul rounded to float8_e4m3, per-tensor scaled.
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
_HI = jax.lax.Precision.HIGHEST
_NEG_INF = float("-inf")


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed >> 31),
                              seed & 0x7FFFFFFF)


def layer_leaf_shapes(spec):
    d, hd = spec["hidden"], spec["head_dim"]
    h, kv = spec["heads"], spec["kv_heads"]
    hi, di = spec["indexer_heads"], spec["indexer_dim"]
    e, w = spec["experts"], spec["expert_width"]
    return {"ln1": (d,), "wq": (d, h * hd), "wk": (d, kv * hd),
            "wv": (d, kv * hd), "q_norm": (hd,), "k_norm": (hd,),
            "wiq": (d, hi * di), "wik": (d, di), "wiw": (d, hi),
            "wo": (h * hd, d), "ln2": (d,), "wr": (d, e),
            "w_gate_up": (e, d, 2 * w), "w_down": (e, w, d)}


def top_leaf_shapes(spec):
    d, v = spec["hidden"], spec["vocab"]
    return {"embed": (v, d), "final_norm": (d,), "lm_head": (d, v)}


def _round_bf16(x):
    """An explicit ``reduce_precision``: XLA drops a float32 -> bfloat16
    -> float32 pair of converts as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.lru_cache(maxsize=None)
def _maker(shapes_json, bf16, stored):
    shapes = {k: tuple(v) for k, v in json.loads(shapes_json)}

    @jax.jit
    def make(key):
        out = {}
        for j, (name, shape) in enumerate(sorted(shapes.items())):
            x = jax.random.normal(jax.random.fold_in(key, j), shape,
                                  jnp.float32)
            if len(shape) == 1:                      # a norm's gain
                x = 1.0 + 0.02 * x
            elif name != "embed":                    # a matrix: 1/sqrt(fan_in)
                x = x * shape[-2] ** -0.5
            if name in bf16:
                # the same values either way: float32 arrays of
                # bfloat16-representable numbers, or bfloat16 arrays
                x = x.astype(jnp.bfloat16) if stored else _round_bf16(x)
            out[name] = x
        return out

    return make


def _make(spec, shapes, key, prefix, stored):
    bf16 = frozenset(name for name in shapes
                     if prefix + name in spec.get("bf16_leaves", ()))
    return _maker(json.dumps(sorted(shapes.items())), bf16, stored)(key)


def init_layer(spec, seed, i, stored=False):
    """Layer ``i``'s weights from the seed, on the default device: float32
    arrays, or with ``stored`` the ``bf16_leaves`` as bfloat16 arrays of
    the same values (what a program that stores them so is loaded with:
    half the bytes, and no float32 copy of a 1.6 GB leaf beside it)."""
    return _make(spec, layer_leaf_shapes(spec),
                 jax.random.fold_in(seed_key(seed), i + 1), "layers.", stored)


def init_top(spec, seed, stored=False):
    """The embedding, the final norm and the untied head."""
    return _make(spec, top_leaf_shapes(spec),
                 jax.random.fold_in(seed_key(seed), 0), "", stored)


# -- the mathematics ---------------------------------------------------------

def _fp8(x):
    scale = 448.0 / (jnp.max(jnp.abs(x)) + 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _einsum(eq, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=_HI)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, positions, theta):
    """``x (S, H, D)``, rotate-half."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def topk_mask(scores, k):
    """Rows of ``scores (Q, S)`` (``-inf`` where s > t): True at the ``k``
    largest entries of each row, equal entries taken from the lowest
    position up; every finite entry where a row has ``k`` or fewer."""
    finite = scores > _NEG_INF
    if scores.shape[-1] <= k:
        return finite
    kth = jax.lax.top_k(scores, k)[0][:, -1:]
    above = scores > kth
    equal = scores == kth
    need = k - jnp.sum(above, -1, keepdims=True)
    taken = equal & (jnp.cumsum(equal, -1) <= need)
    return (above | taken) & finite


def _attention(a, lp, spec, precision):
    """``a (S, d)`` normed hidden states -> concat(o) ``(S, heads *
    head_dim)``; S is a multiple of QUERY_BLOCK."""
    s_len = a.shape[0]
    h, kv, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
    hi, di = spec["indexer_heads"], spec["indexer_dim"]
    theta, eps = spec["rope_theta"], spec["rms_eps"]
    pos = jnp.arange(s_len)
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)

    q = _rope(_rms_norm(mm(a, lp["wq"]).reshape(s_len, h, hd),
                        lp["q_norm"], eps), pos, theta)
    k = _rope(_rms_norm(mm(a, lp["wk"]).reshape(s_len, kv, hd),
                        lp["k_norm"], eps), pos, theta)
    v = mm(a, lp["wv"]).reshape(s_len, kv, hd)
    q_idx = _rope(mm(a, lp["wiq"]).reshape(s_len, hi, di), pos, theta)
    k_idx = _rope(mm(a, lp["wik"]).reshape(s_len, 1, di), pos, theta)[:, 0]
    w_idx = mm(a, lp["wiw"]) * (hi * di) ** -0.5

    def block(start):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=start, slice_size=QUERY_BLOCK)
        t = start + jnp.arange(QUERY_BLOCK)
        seen = pos[None, :] <= t[:, None]
        dots = _einsum("qhd,sd->qhs", sl(q_idx), k_idx, precision)
        index = jnp.einsum("qhs,qh->qs", jax.nn.relu(dots), sl(w_idx),
                           precision=_HI)
        chosen = topk_mask(jnp.where(seen, index, _NEG_INF), spec["topk"])
        qb = sl(q).reshape(QUERY_BLOCK, kv, h // kv, hd)
        logits = _einsum("qgrd,sgd->grqs", qb, k, precision) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[None, None], logits, _NEG_INF),
                           axis=-1)
        o = _einsum("grqs,sgd->qgrd", p, v, precision)
        return o.reshape(QUERY_BLOCK, h * hd)

    starts = jnp.arange(0, s_len, QUERY_BLOCK)
    return jax.lax.map(block, starts).reshape(s_len, h * hd)


def route(b, wr, spec):
    """Gates ``(S, experts)``: ``p_e / sum_E p`` on the token's top-k
    experts, 0 elsewhere. The router's product is never rounded for the
    control: it picks experts, it is not one of the model's matmuls in
    the precision the configuration states."""
    p = jax.nn.softmax(jnp.dot(b, wr, precision=_HI), axis=-1)
    top_p, top_e = jax.lax.top_k(p, spec["experts_per_token"])
    if spec["norm_topk"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    rows = jnp.arange(b.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, top_e].set(top_p)


def _routed_ffn(b, lp, spec, precision):
    width = spec["expert_width"]
    gates = route(b, lp["wr"], spec)
    mm = functools.partial(_einsum, "sd,de->se", precision=precision)

    def expert(y, e):
        w_gu = lp["w_gate_up"][e]
        hidden = (jax.nn.silu(mm(b, w_gu[:, :width]))
                  * mm(b, w_gu[:, width:]))
        return y + gates[:, e, None] * mm(hidden, lp["w_down"][e]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(b),
                        jnp.arange(spec["experts"]))
    return y


@functools.partial(jax.jit, static_argnames=("spec_json", "precision"))
def _layer(x, lp, spec_json, precision):
    spec = json.loads(spec_json)
    eps = spec["rms_eps"]
    o = _attention(_rms_norm(x, lp["ln1"], eps), lp, spec, precision)
    x = x + _einsum("sd,de->se", o, lp["wo"], precision)
    return x + _routed_ffn(_rms_norm(x, lp["ln2"], eps), lp, spec, precision)


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

    def timed(key, fn, *args):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
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
            hidden = [timed("layers_s", _layer, x, lp, spec_json, precision)
                      for x in hidden]
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
    """What ``postln_transformer.served_token_gaps`` returns, for this
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
