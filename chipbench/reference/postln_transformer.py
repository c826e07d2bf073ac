"""The plain reference: a post-LN transformer stack in float32 jax.numpy.

Written from the papers (Vaswani et al. 2017 for the block; Devlin et al.
2018 for the BERT embeddings, heads and loss), independent of
``simple_tensorflow_tpu``: it imports nothing of the program and is given
nothing the program made. Weights come from :func:`init_params` (one
jitted call from the seed), inputs from the harness's generators.

Two shapes of one block, chosen by ``spec["kind"]``:

``bert``       bidirectional attention with a key-padding mask, learned
               position + token-type embeddings, GELU (tanh form), masked-LM
               head tied to the word embeddings + next-sentence head, the
               pretraining loss, its gradients and TF-style Adam.
``causal_lm``  causal attention, sinusoidal positions, embeddings scaled by
               sqrt(d_model), ReLU, output head tied to the embeddings.

``precision``: ``"f32"`` is the reference (every matmul at
``jax.lax.Precision.HIGHEST``); ``"fp8"`` is the CONTROL — the same
mathematics with both operands of every matmul rounded to float8_e4m3
(per-tensor scaled, straight-through in the backward pass), the precision
below the bfloat16 the configurations state.

Parameters are stored per leaf in float32; leaves the configuration stores
in bfloat16 are rounded to bfloat16 values at init and after every Adam
update (``spec["bf16_leaves"]``), as the configuration states — there is no
float32 master copy in it.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo",
                "ln1_g", "ln1_b", "w1", "b1", "w2", "b2", "ln2_g", "ln2_b")


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62 (a plain
    ``PRNGKey(seed)`` overflows int32 above 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed >> 31),
                              seed & 0x7FFFFFFF)


def leaf_shapes(spec):
    """{leaf name: shape}; per-layer leaves carry a leading [layers] axis."""
    d, f, n = spec["hidden"], spec["ffn"], spec["layers"]
    v = spec["vocab"]
    shapes = {
        "layers.wq": (n, d, d), "layers.bq": (n, d),
        "layers.wk": (n, d, d), "layers.bk": (n, d),
        "layers.wv": (n, d, d), "layers.bv": (n, d),
        "layers.wo": (n, d, d), "layers.bo": (n, d),
        "layers.ln1_g": (n, d), "layers.ln1_b": (n, d),
        "layers.w1": (n, d, f), "layers.b1": (n, f),
        "layers.w2": (n, f, d), "layers.b2": (n, d),
        "layers.ln2_g": (n, d), "layers.ln2_b": (n, d),
        "emb.word": (v, d),
    }
    if spec["kind"] == "bert":
        shapes.update({
            "emb.pos": (spec["max_position"], d),
            "emb.type": (spec["type_vocab"], d),
            "emb.ln_g": (d,), "emb.ln_b": (d,),
            "pool.w": (d, d), "pool.b": (d,),
            "mlm.w": (d, d), "mlm.b": (d,),
            "mlm.ln_g": (d,), "mlm.ln_b": (d,), "mlm.out_b": (v,),
            "nsp.w": (d, 2), "nsp.b": (2,),
        })
    return shapes


def _leaf_std(spec, name, shape):
    """Seeded-normal scale per leaf: the scale each family initialises at
    (BERT: 0.02 everywhere; Transformer: Glorot for matrices, d^-0.5 for
    the embedding). Biases and LN shifts get a small scale of their own so
    that no leaf is identically zero and every row of the check differs."""
    if name.endswith("_g"):
        return 0.02  # around 1.0, see init_params
    if spec["kind"] == "bert":
        return 0.02
    if name == "emb.word":
        return spec["hidden"] ** -0.5
    if len(shape) >= 2 and name.split(".")[-1].startswith("w"):
        return math.sqrt(2.0 / (shape[-2] + shape[-1]))
    return 0.02


def _round_bf16(x):
    """Round float32 to the nearest bfloat16 value. As an explicit
    ``reduce_precision``: XLA removes a float32 -> bfloat16 -> float32
    pair of converts as excess precision (on the v5e it did: a reference
    "in bfloat16" read exactly the float32 one, PERF.md Findings)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def init_params(spec, seed):
    """All weights in ONE jitted call from the seed. Float32 arrays;
    ``spec["bf16_leaves"]`` hold bfloat16-representable values."""
    shapes = leaf_shapes(spec)
    names = sorted(shapes)
    bf16 = set(spec.get("bf16_leaves", ()))

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape = shapes[name]
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * _leaf_std(spec, name, shape)
            if name.endswith("_g"):
                x = 1.0 + x
            out[name] = _round_bf16(x) if name in bf16 else x
        return out

    return make(seed_key(seed))


# -- the mathematics ---------------------------------------------------------

def _fp8(x):
    """Round to float8_e4m3 with a per-tensor scale; identity gradient."""
    amax = jnp.max(jnp.abs(x)) + 1e-30
    scale = 448.0 / amax
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(h, lp, mask_bias, spec, precision):
    """One post-LN block. h (B,S,D); mask_bias broadcastable to
    (B,heads,S,S), additive."""
    b, s, d = h.shape
    heads = spec["heads"]
    hd = d // heads
    mm = functools.partial(_mm, precision=precision)

    def split(x):
        return x.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)

    q = split(mm(h, lp["wq"]) + lp["bq"])
    k = split(mm(h, lp["wk"]) + lp["bk"])
    v = split(mm(h, lp["wv"]) + lp["bv"])
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(hd) + mask_bias
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = mm(probs, v).transpose(0, 2, 1, 3).reshape(b, s, d)
    a = mm(ctx, lp["wo"]) + lp["bo"]
    h = _layer_norm(h + a, lp["ln1_g"], lp["ln1_b"], spec["ln_eps"])
    act = _gelu_tanh if spec["activation"] == "gelu" else jax.nn.relu
    f = mm(act(mm(h, lp["w1"]) + lp["b1"]), lp["w2"]) + lp["b2"]
    return _layer_norm(h + f, lp["ln2_g"], lp["ln2_b"], spec["ln_eps"])


def _stack(h, params, mask_bias, spec, precision):
    layers = {k.split(".", 1)[1]: v for k, v in params.items()
              if k.startswith("layers.")}

    @jax.checkpoint
    def body(hh, lp):
        return _block(hh, lp, mask_bias, spec, precision), None

    h, _ = jax.lax.scan(body, h, layers)
    return h


def sinusoid(length, d):
    pos = np.arange(length)[:, None].astype(np.float64)
    dim = np.arange(d // 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, 2.0 * dim / d)
    enc = np.zeros((length, d), np.float32)
    enc[:, 0::2] = np.sin(angle)
    enc[:, 1::2] = np.cos(angle)
    return enc


def causal_lm_hidden(params, ids, spec, precision="f32"):
    """ids (B,S) -> final hidden states (B,S,D)."""
    b, s = ids.shape
    d = spec["hidden"]
    h = params["emb.word"][ids] * math.sqrt(d) + sinusoid(s, d)[None]
    causal = jnp.where(jnp.arange(s)[None, :] <= jnp.arange(s)[:, None],
                       0.0, -1e9)[None, None]
    return _stack(h, params, causal, spec, precision)


def causal_lm_logits(params, ids, spec, precision="f32"):
    h = causal_lm_hidden(params, ids, spec, precision)
    return _mm(h, params["emb.word"].T, precision)


def bert_loss(params, batch, spec, precision="f32"):
    """Masked-LM + next-sentence pretraining loss of Devlin et al."""
    ids = batch["input_ids"]
    b, s = ids.shape
    mm = functools.partial(_mm, precision=precision)
    h = (params["emb.word"][ids] + params["emb.type"][batch["token_type_ids"]]
         + params["emb.pos"][:s][None])
    h = _layer_norm(h, params["emb.ln_g"], params["emb.ln_b"],
                    spec["ln_eps"])
    mask = batch["input_mask"].astype(jnp.float32)
    bias = ((1.0 - mask) * -1e9)[:, None, None, :]
    h = _stack(h, params, bias, spec, precision)

    pooled = jnp.tanh(mm(h[:, 0], params["pool.w"]) + params["pool.b"])
    nsp_logits = mm(pooled, params["nsp.w"]) + params["nsp.b"]
    nsp = -jnp.take_along_axis(jax.nn.log_softmax(nsp_logits),
                               batch["nsp_labels"][:, None], 1)[:, 0]

    x = jnp.take_along_axis(h, batch["mlm_positions"][:, :, None], 1)
    x = _gelu_tanh(mm(x, params["mlm.w"]) + params["mlm.b"])
    x = _layer_norm(x, params["mlm.ln_g"], params["mlm.ln_b"],
                    spec["ln_eps"])
    logits = mm(x, params["emb.word"].T) + params["mlm.out_b"]
    per_tok = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                   batch["mlm_ids"][:, :, None], 2)[..., 0]
    w = batch["mlm_weights"]
    return jnp.sum(per_tok * w) / (jnp.sum(w) + 1e-5) + jnp.mean(nsp)


# -- training: loss, gradients, Adam ------------------------------------------

@functools.lru_cache(maxsize=None)
def _bert_grad_fn(spec_json, precision):
    spec = json.loads(spec_json)
    return jax.jit(jax.value_and_grad(
        lambda p, batch: bert_loss(p, batch, spec, precision)))


def bert_loss_and_grads(params, batch, spec, precision="f32", rows=None):
    """Loss and gradients of one batch. ``rows``: restrict to these rows
    and take every mean over them only (the planted half-batch fault)."""
    if rows is not None:
        batch = {k: v[rows] for k, v in batch.items()}
    return _bert_grad_fn(json.dumps(spec, sort_keys=True), precision)(
        params, batch)


@functools.partial(jax.jit, static_argnames=("bf16", "lr", "b1", "b2", "eps"))
def adam_update(params, m, v, grads, t, *, bf16, lr, b1, b2, eps):
    """TF-style Adam (Kingma & Ba, "epsilon hat" form), step number t>=1.
    Leaves in ``bf16`` are stored rounded to bfloat16; Adam sees the
    gradient rounded the same way for them (it arrives in the leaf's
    type)."""
    alpha = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    new_p, new_m, new_v = {}, {}, {}
    for k in params:
        g = _round_bf16(grads[k]) if k in bf16 else grads[k]
        new_m[k] = b1 * m[k] + (1.0 - b1) * g
        new_v[k] = b2 * v[k] + (1.0 - b2) * jnp.square(g)
        p = params[k] - alpha * new_m[k] / (jnp.sqrt(new_v[k]) + eps)
        new_p[k] = _round_bf16(p) if k in bf16 else p
    return new_p, new_m, new_v


def split_leaves(tree):
    """{leaf: array} with stacked per-layer leaves split into
    ``layers.<i>.<name>``: the granularity the comparison works at."""
    out = {}
    for k, v in tree.items():
        if k.startswith("layers."):
            for i in range(v.shape[0]):
                out[f"layers.{i}.{k.split('.', 1)[1]}"] = v[i]
        else:
            out[k] = v
    return out


def leaf_norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in split_leaves(tree).items()}


def bert_train_reference(spec, seed, batches, job, precision="f32",
                         fault=None):
    """Follow the first ``len(batches)`` steps from the seed's weights.

    Returns per-step losses, per-leaf norms of the first gradient as Adam
    gets it, and per-leaf norms of the parameters' change after the last
    step, and the first gradient itself (``grad1``: host numpy, per leaf,
    flat). ``fault="half_batch"`` leaves the second half of every
    batch out and takes the means over the rest."""
    bf16 = frozenset(spec.get("bf16_leaves", ()))
    params0 = init_params(spec, seed)
    params = params0
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad1 = [], None
    for t, batch in enumerate(batches, start=1):
        batch = {k: jnp.asarray(x) for k, x in batch.items()}
        rows = None
        if fault == "half_batch":
            rows = slice(0, batch["input_ids"].shape[0] // 2)
        loss, grads = bert_loss_and_grads(params, batch, spec, precision,
                                          rows)
        losses.append(float(loss))
        if t == 1:
            as_adam_gets_it = {k: (_round_bf16(g) if k in bf16 else g)
                               for k, g in grads.items()}
            grad1 = {k: np.asarray(g, np.float32).reshape(-1)
                     for k, g in split_leaves(as_adam_gets_it).items()}
        params, m, v = adam_update(
            params, m, v, grads, float(t), bf16=bf16,
            lr=job["learning_rate"], b1=job["beta1"], b2=job["beta2"],
            eps=job["epsilon"])
    change = leaf_norms({k: params[k] - params0[k] for k in params})
    return {"losses": losses, "grad1": grad1, "change_norms": change,
            "grad1_norms": {k: float(np.linalg.norm(g.astype(np.float64)))
                            for k, g in grad1.items()}}


# -- serving: gaps under the reference's best ---------------------------------

@functools.partial(jax.jit, static_argnames=("spec_json", "prec"))
def _logits_at(params, ids, pos, spec_json, prec):
    spec = json.loads(spec_json)
    h = causal_lm_hidden(params, ids, spec, prec)
    hs = jnp.take_along_axis(h, pos[:, :, None], 1)
    return _mm(hs, params["emb.word"].T, prec)


def served_token_gaps(spec, params, prompts, served, pad_to=None,
                      control=None, n_out=None):
    """For each request (prompt ids, served greedy tokens) run ONE forward
    pass over prompt + served tokens and return, per request, a dict of
    arrays over the positions that emitted a served token:

    ``gap``        max(logits) - logits[served token] (0 when the served
                   token is the reference's best);
    ``logprob``    the reference's log-probability of the served token;
    ``margin``     the reference's best logit minus its second best;
    ``second``     the token the reference puts second (the planted
                   second-best fault serves it, chipbench/calibrate.py).

    ``control``: a precision name; adds ``control_gap`` — at each of the
    same positions, the gap of the token THAT precision puts first — and
    ``control_logprob``, that precision's own log-probability of the
    served token."""
    pad_to = pad_to or max(len(p) + len(s) for p, s in zip(prompts, served))
    n_out = n_out or max(len(s) for s in served)
    spec_json = json.dumps(spec, sort_keys=True)
    out = []
    for prompt, toks in zip(prompts, served):
        n = len(toks)
        seq = np.zeros((1, pad_to), np.int32)
        full = list(prompt) + list(toks)
        seq[0, :len(full)] = full
        # the position that emitted served token j is len(prompt)-1+j
        pos = np.zeros((1, n_out), np.int32)
        pos[0, :n] = len(prompt) - 1 + np.arange(n)
        seq, pos = jnp.asarray(seq), jnp.asarray(pos)
        ref = _logits_at(params, seq, pos, spec_json, "f32")[0]
        tok = jnp.asarray(np.pad(np.asarray(toks, np.int32), (0, n_out - n)))
        best2, best2_tok = jax.lax.top_k(ref, 2)
        at = lambda logits, t: jnp.take_along_axis(  # noqa: E731
            logits, t[:, None], 1)[:, 0]
        row = {"gap": best2[:, 0] - at(ref, tok),
               "logprob": at(jax.nn.log_softmax(ref), tok),
               "margin": best2[:, 0] - best2[:, 1],
               "second": best2_tok[:, 1]}
        if control is not None:
            low = _logits_at(params, seq, pos, spec_json, control)[0]
            row["control_gap"] = best2[:, 0] - at(ref, jnp.argmax(low, -1))
            row["control_logprob"] = at(jax.nn.log_softmax(low), tok)
        out.append({k: np.asarray(v)[:n] for k, v in row.items()})
    return out
