"""chip_smoke.py — the quickest proof that stf still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the full width of models the repo has, on ONE TPU chip in ONE
process:

  trainer  BERT-base (12 x 768, vocab 30522), seq 512, batch 24, bf16:
           stf.Session -> init -> 3 x run([train_op, loss]) -> one
           run_steps(n=4) window
  kernels  each of the seven Pallas kernels (ops/pallas), forward and
           backward where it has one, against its own reference
  server   CausalLMGenerativeModel at Transformer-big widths (1024,
           16 x 64, d_ff 4096) -> ModelServer.load_generative -> eight
           streamed greedy generations, two checked token for token
           against a plain re-forward on the same weights
  example  examples/train_mnist_end_to_end.py --steps 12, in-process
           (records -> stf.data arena staging -> MonitoredTrainingSession
           -> checkpoint resume -> SavedModel -> predict server)

``--chips 4`` runs ONLY the multi-chip phase and what it is compared
with (dp=4 BERT train step vs one device; tp=4 decode vs tp=1).

Every line before the last is one JSON object of set-up facts (NOT
benchmark results: nothing here is a steady-state timing). A phase that
fails raises. The last line is the contract line
``{"ok": true, "device": {...}}``; exit code 0 only with it.

The phases are functions of the model configuration so a CPU rehearsal
can import them with the models' tiny() configs (see
.claude/skills/verify/SKILL.md); this script itself has no size or
platform switch and refuses to run without a TPU.
"""

import argparse
import dataclasses
import json
import math
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


def emit(phase, **facts):
    print(json.dumps({"phase": phase, "kind": "setup_fact", **facts},
                     default=str), flush=True)


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included) and persistent-cache hits/misses, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as jm

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jm.register_event_duration_secs_listener(self._on_duration)
        jm.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (time.perf_counter(), self.compile_s, self.hits, self.misses)

    def since(self, mark):
        wall = time.perf_counter() - mark[0]
        comp = self.compile_s - mark[1]
        return {"wall_s": round(wall, 2), "compile_s": round(comp, 2),
                "run_s": round(wall - comp, 2),
                "cache_hits": self.hits - mark[2],
                "cache_misses": self.misses - mark[3]}


def _memory_stat(key):
    import jax

    return [int((d.memory_stats() or {}).get(key, 0))
            for d in jax.devices()]


def peak_bytes():
    return _memory_stat("peak_bytes_in_use")


def _bytes_in_use():
    import gc

    gc.collect()  # buffers of closed sessions are freed on collection
    return _memory_stat("bytes_in_use")


# ---------------------------------------------------------------------------
# native runtime: built from the committed sources
# ---------------------------------------------------------------------------

def phase_native():
    subprocess.run(["make", "-C", os.path.join(ROOT, "runtime_cc"),
                    "clean"], check=True, capture_output=True)
    from simple_tensorflow_tpu.runtime import native

    assert native.available(), "native runtime did not build/load"
    return {"native_available": True, "native_version": native.version()}


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def _bert_graph(cfg, batch, seq_len, data_parallel=False):
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import bert

    max_pred = max(1, int(seq_len * 0.15))
    m = bert.bert_pretrain_model(
        batch_size=batch, seq_len=seq_len, max_predictions=max_pred,
        cfg=cfg, compute_dtype=stf.bfloat16, use_input_mask=True,
        data_parallel=data_parallel)
    batch_np = bert.synthetic_pretrain_batch(
        batch, seq_len, max_pred, vocab_size=cfg.vocab_size, seed=0)
    batch_np["input_mask"] = np.ones((batch, seq_len), np.int32)
    feed = {m[k]: v for k, v in batch_np.items()}
    return m, feed


def _counter(name, *labels):
    """Current value of one cell of a registered Counter."""
    from simple_tensorflow_tpu.platform import monitoring

    return monitoring.get_metric(name).get_cell(*labels).value()


def phase_trainer(cfg, batch, seq_len, backend):
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.kernels import registry as kreg

    stf.reset_default_graph()
    stf.set_random_seed(0)
    m, feed = _bert_graph(cfg, batch, seq_len)
    folded0 = _counter("/stf/graph/optimizer/plan_folded_ops")
    facts = {"model": f"bert {cfg.num_layers}x{cfg.hidden_size} "
                      f"vocab {cfg.vocab_size}",
             "batch": batch, "seq_len": seq_len, "dtype": "bfloat16"}
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        fetches = [m["train_op"], m["loss"]]
        plan = sess.plan(fetches, feeds=list(feed))
        facts["plan_folded_ops"] = \
            _counter("/stf/graph/optimizer/plan_folded_ops") - folded0
        exe = plan.compile()
        ma = exe.memory_analysis()
        facts["memory_analysis"] = {
            k: int(getattr(ma, f"{k}_size_in_bytes", 0))
            for k in ("argument", "output", "temp", "alias",
                      "generated_code")}
        if backend == "tpu":
            assert "tpu_custom_call" in exe.hlo_text, \
                "no tpu_custom_call in the train step's HLO"
        facts["tpu_custom_calls_in_step"] = \
            exe.hlo_text.count("tpu_custom_call")
        losses = []
        for _ in range(3):
            _, loss = sess.run(fetches, feed_dict=feed)
            losses.append(float(loss))
        gs3 = int(sess.run(m["global_step"]))
        _, window = sess.run_steps(fetches, n=4, feed_dict=feed,
                                   output_mode="stacked")
        window = [float(x) for x in np.asarray(window)]
        gs7 = int(sess.run(m["global_step"]))
        fused = _counter("/stf/session/fused_steps_amortized")
    facts.update(losses=losses, run_steps_losses=window,
                 global_step_after_runs=gs3,
                 global_step_after_window=gs7,
                 fused_steps_amortized=fused)
    expect = math.log(cfg.vocab_size) + math.log(2.0)
    facts["expected_first_loss"] = round(expect, 4)
    assert all(np.isfinite(losses + window)), (losses, window)
    assert abs(losses[0] - expect) / expect < 0.05, (losses[0], expect)
    assert (gs3, gs7) == (3, 7), (gs3, gs7)
    assert fused >= 4, "run_steps fell back to sequential runs"
    # the window continues the trajectory: same batch every step, so
    # the loss keeps falling from where the sequential steps left it
    assert window[0] < losses[0] and window[-1] < losses[-1], \
        (losses, window)

    snap = kreg.snapshot()
    facts["kernel_registry"] = snap
    facts["kernel_decisions"] = kreg.decisions_snapshot()
    assert snap["backend"] == backend, snap["backend"]
    flash = sum(n for op, n in snap["routed"].items()
                if op.startswith("FlashAttention"))
    if backend == "tpu":
        interp = [k for k in snap["fallback"]
                  if k.endswith(":interpret_backend")]
        assert not interp, f"interpret_backend fallbacks on a TPU: {interp}"
        assert flash > 0, "flash attention was not routed to Pallas"
    return facts


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _err(got, ref):
    g = np.asarray(got, np.float32)
    r = np.asarray(ref, np.float32)
    assert g.shape == r.shape, (g.shape, r.shape)
    assert np.all(np.isfinite(g)), "non-finite kernel output"
    return float(np.max(np.abs(g - r)) / (np.max(np.abs(r)) + 1e-30))


def _tol(dtype):
    # normalised max error |got-ref|_inf / |ref|_inf per input dtype
    return 2e-2 if np.dtype(dtype).itemsize == 2 else 2e-3


def phase_kernels(bert_cfg, lm_cfg, batch, seq_len, lm_batch, lm_cache_len,
                  page_len):
    """Each Pallas kernel at the shapes the two models produce, against
    its own reference (references evaluate under highest matmul
    precision so an f32 comparison is f32 on both sides)."""
    import jax
    import jax.numpy as jnp

    from simple_tensorflow_tpu.ops import pallas as P

    rng = np.random.RandomState(0)
    bf16, f32 = jnp.bfloat16, jnp.float32
    results = {}

    def arr(shape, dt, scale=1.0):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale,
                           dtype=dt)

    def check(name, fn, ref, args, dt, grad_argnums=None, exact=False):
        out = jax.jit(fn)(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*args)
        outs = out if isinstance(out, (tuple, list)) else [out]
        wants = want if isinstance(want, (tuple, list)) else [want]
        errs = [_err(o, w) for o, w in zip(outs, wants)]
        if exact:
            assert all(np.array_equal(np.asarray(o), np.asarray(w))
                       for o, w in zip(outs, wants)), f"{name}: not exact"
        if grad_argnums is not None:
            w = arr(np.shape(outs[0]), f32)

            def scalar(f):
                def s(*a):
                    o = f(*a)
                    o = o[0] if isinstance(o, (tuple, list)) else o
                    return jnp.sum(o.astype(f32) * w)
                return s

            g = jax.jit(jax.grad(scalar(fn), grad_argnums))(*args)
            with jax.default_matmul_precision("highest"):
                gr = jax.jit(jax.grad(scalar(ref), grad_argnums))(*args)
            errs += [_err(a, b) for a, b in zip(g, gr)]
        tol = 0.0 if exact else _tol(dt)
        assert max(errs) <= tol, f"{name}: max error {max(errs)} > {tol}"
        results[name] = {"max_err": max(errs), "tol": tol,
                         "grads": grad_argnums is not None}

    # -- flash attention -----------------------------------------------------
    hb, hd = bert_cfg.num_heads, bert_cfg.hidden_size // bert_cfg.num_heads
    q, k, v = (arr((batch, hb, seq_len, hd), bf16) for _ in range(3))
    check("flash_attention/fwd_bert", P.flash_attention, P.attention_xla,
          (q, k, v), bf16)
    bias = jnp.where(jnp.arange(seq_len)[None, :] < seq_len - 7, 0.0,
                     -1e9).astype(f32) * jnp.ones((batch, 1), f32)
    seed = jnp.asarray([1234], jnp.int32)
    check("flash_attention/fwd_bias_dropout_bert",
          lambda q, k, v, b: P.flash_attention(
              q, k, v, bias=b, dropout_rate=0.1, dropout_seed=seed),
          lambda q, k, v, b: P.attention_xla(
              q, k, v, bias=b, dropout_rate=0.1, dropout_seed=seed),
          (q, k, v, bias), bf16, grad_argnums=(0, 1, 2))
    hl, dl = lm_cfg.num_heads, lm_cfg.d_model // lm_cfg.num_heads
    ql, kl, vl = (arr((lm_batch, hl, seq_len, dl), bf16) for _ in range(3))
    check("flash_attention/causal_bwd_lm",
          lambda q, k, v: P.flash_attention(q, k, v, causal=True),
          lambda q, k, v: P.mha_reference(q, k, v, causal=True),
          (ql, kl, vl), bf16, grad_argnums=(0, 1, 2))

    # -- layer norm ----------------------------------------------------------
    rows, hid = batch * seq_len, bert_cfg.hidden_size
    x = arr((rows, hid), bf16)
    gamma, beta = 1.0 + arr((hid,), f32, 0.1), arr((hid,), f32, 0.1)
    check("layer_norm/fwd_bwd",
          lambda x, g, b: P.layer_norm(x, g, b, eps=bert_cfg.layer_norm_eps),
          lambda x, g, b: P.layer_norm_reference(
              x, g, b, eps=bert_cfg.layer_norm_eps),
          (x, gamma, beta), bf16, grad_argnums=(0, 1, 2))

    # -- softmax cross-entropy ----------------------------------------------
    n_pred = batch * max(1, int(seq_len * 0.15))
    logits = arr((n_pred, bert_cfg.vocab_size), bf16)
    labels = jnp.asarray(rng.randint(0, bert_cfg.vocab_size, n_pred),
                         jnp.int32)
    check("softmax_xent/fwd_bwd",
          lambda lg: P.softmax_cross_entropy(lg, labels),
          lambda lg: P.softmax_cross_entropy_reference(lg, labels),
          (logits,), bf16, grad_argnums=(0,))

    # -- fused optimizer updates on one flat group ---------------------------
    n = (bert_cfg.vocab_size * hid
         + bert_cfg.num_layers * (4 * hid * hid
                                  + 2 * hid * bert_cfg.intermediate_size))
    p, g = arr((n,), f32), arr((n,), f32)
    mm, vv = arr((n,), f32, 0.01), jnp.abs(arr((n,), f32, 0.01))
    alpha = jnp.asarray(1e-3, f32)
    check("fused_update/adam",
          lambda *a: P.adam_update(*a, beta1=0.9, beta2=0.999, eps=1e-8),
          lambda *a: P.adam_update_reference(*a, beta1=0.9, beta2=0.999,
                                             eps=1e-8),
          (p, mm, vv, g, alpha), f32)
    check("fused_update/momentum", P.momentum_update,
          P.momentum_update_reference,
          (p, mm, g, jnp.asarray(0.01, f32), jnp.asarray(0.9, f32)), f32)
    del p, g, mm, vv

    # -- dropout + bias + residual: the counter-based mask is bit-exact ------
    res, bvec = arr((rows, hid), bf16), arr((hid,), bf16)
    check("dropout_bias_residual",
          lambda x, r, b: P.dropout_bias_residual(x, r, b, rate=0.1,
                                                  seed=seed),
          lambda x, r, b: P.dropout_bias_residual_reference(
              x, r, b, rate=0.1, seed=seed),
          (x, res, bvec), bf16, exact=True)

    # -- int8 matmul ---------------------------------------------------------
    xm = arr((512, lm_cfg.d_model), bf16)
    wq, w_scale = P.quantize_colwise(arr((lm_cfg.d_model, lm_cfg.d_ff), f32))
    check("quant_matmul", P.quant_matmul, P.quant_matmul_reference,
          (xm, wq, w_scale), bf16)

    # -- decode attention: single query and query block ----------------------
    lengths = jnp.asarray(
        rng.randint(1, lm_cache_len - page_len, lm_batch), jnp.int32)
    for dt in (bf16, f32):
        kc, vc = (arr((lm_batch, lm_cache_len, hl, dl), dt)
                  for _ in range(2))
        tag = jnp.dtype(dt).name
        check(f"decode_attention/single_query_{tag}",
              lambda q, k, v: P.decode_attention(q, k, v, lengths),
              lambda q, k, v: P.decode_attention_xla(q, k, v, lengths),
              (arr((lm_batch, hl, dl), dt), kc, vc), dt)
        check(f"decode_attention/query_block_{tag}",
              lambda q, k, v: P.decode_attention(
                  q, k, v, lengths, causal_offset=True),
              lambda q, k, v: P.decode_attention_xla(
                  q, k, v, lengths, causal_offset=True),
              (arr((lm_batch, page_len, hl, dl), dt), kc, vc), dt)
    return {"kernels": results}


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def _reforward_greedy(cfg, ckpt, prompts, steps, compute_dtype, length):
    """The plain reference: full re-forward through causal_lm_logits +
    argmax per emitted token, on the weights of ``ckpt``. Returns one
    [(token, its log-probability), ...] stream per prompt."""
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import causal_lm as clm

    g = stf.Graph()
    with g.as_default():
        ids = stf.placeholder(stf.int32, [1, length], "ids")
        logits = clm.causal_lm_logits(ids, cfg, training=False,
                                      compute_dtype=compute_dtype)
        logp = stf.nn.log_softmax(stf.cast(logits, stf.float32))
        nxt = stf.argmax(logp, 2, output_type=stf.int32)
        best = stf.reduce_max(logp, 2)
        with stf.Session(graph=g) as sess:
            stf.train.Saver().restore(sess, ckpt)
            streams = []
            for prompt in prompts:
                seq, out = list(prompt), []
                for _ in range(steps):
                    row = np.full((1, length), cfg.pad_id, np.int32)
                    row[0, :len(seq)] = seq
                    tok, lp = sess.run([nxt, best], {ids: row})
                    out.append((int(tok[0, len(seq) - 1]),
                                float(lp[0, len(seq) - 1])))
                    seq.append(out[-1][0])
                streams.append(out)
    return streams


def _agreement(a, b):
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


def phase_server(cfg, compute_dtype, *, page_len, pages_per_seq, max_live,
                 new_tokens, exact, tp=None, n_prompts=8, n_checked=2,
                 model_name="lm", checkpoint=None):
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import serving
    from simple_tensorflow_tpu.kernels import registry as kreg
    from simple_tensorflow_tpu.models import causal_lm as clm

    max_seq = page_len * pages_per_seq
    num_pages = max_live * pages_per_seq
    facts = {"model": f"causal_lm {cfg.num_layers}x{cfg.d_model} "
                      f"heads {cfg.num_heads} d_ff {cfg.d_ff} "
                      f"vocab {cfg.vocab_size}",
             "dtype": compute_dtype.name, "tp": tp or 1,
             "page_len": page_len, "num_pages": num_pages,
             "max_live": max_live, "max_seq_len": max_seq}
    used0 = _bytes_in_use()
    model = clm.CausalLMGenerativeModel(
        cfg, page_len=page_len, pages_per_seq=pages_per_seq,
        num_pages=num_pages, max_live=max_live,
        compute_dtype=compute_dtype, init_fresh=checkpoint is None,
        checkpoint=checkpoint, seed=0, tp=tp)
    facts["cache_bytes_total"] = int(model._cache_bytes_total)
    facts["model_bytes_per_device"] = [
        b - a for a, b in zip(used0, _bytes_in_use())]
    tmp = tempfile.mkdtemp(prefix="stf_chip_smoke_")
    with model.graph.as_default():
        ckpt = stf.train.Saver().save(model.session,
                                      os.path.join(tmp, "model"))
    facts["checkpoint"] = ckpt

    # eight prompts of different lengths; the first two share a prefix
    # of two whole pages (the second admission must hit the first's)
    rng = np.random.RandomState(0)
    shared = list(rng.randint(2, cfg.vocab_size, 2 * page_len))
    room = max_seq - new_tokens - 2 * page_len - 1
    assert room >= n_prompts, "cache too short for the prompts"
    prompts = []
    for i in range(n_prompts):
        tail = list(rng.randint(2, cfg.vocab_size,
                                1 + (i * room) // n_prompts))
        head = shared if i < 2 else list(
            rng.randint(2, cfg.vocab_size, page_len // 2 + i))
        prompts.append([int(t) for t in head + tail])
    facts["prompt_lens"] = [len(p) for p in prompts]

    tokens0 = _counter("/stf/serving/decode_tokens", model_name)
    server = serving.ModelServer()
    try:
        server.load_generative(model, model_name)
        streamed = [[] for _ in prompts]
        logps = [[] for _ in prompts]
        futs = []
        for i, p in enumerate(prompts):
            futs.append(server.generate(
                p, model=model_name, max_new_tokens=new_tokens,
                on_token=lambda tok, lp, i=i: (
                    streamed[i].append(int(tok)),
                    logps[i].append(float(lp)))))
        results = [f.result(timeout=900) for f in futs]
        status = [r for r in server.statusz_info()
                  if r.get("model") == model_name][0]
        tokens = _counter("/stf/serving/decode_tokens",
                          model_name) - tokens0
    finally:
        server.close()
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("stf_serving_")]
    assert not leaked, f"serving threads survive close(): {leaked}"

    delivered = sum(len(s) for s in streamed)
    facts.update(outcomes=[r["outcome"] for r in results],
                 tokens_delivered=delivered, decode_tokens_metric=tokens,
                 prefix_cache=status["prefix_cache"])
    for r, s in zip(results, streamed):
        assert r["outcome"] in ("eos", "length"), r["outcome"]
        assert list(r["tokens"]) == s, "streamed tokens != future's tokens"
        assert r["outcome"] == "eos" or len(s) == new_tokens
    assert tokens == delivered, (tokens, delivered)
    assert status["prefix_cache"]["hit_pages"] > 0, status["prefix_cache"]

    ref = _reforward_greedy(cfg, ckpt, prompts[:n_checked], new_tokens,
                            compute_dtype, max_seq)
    ref_toks = [[t for t, _ in r] for r in ref]
    agree = [_agreement(s, r) for s, r in zip(streamed, ref_toks)]
    # a random-weight model soon repeats one token, so equal tokens
    # alone say little: the streamed log-probabilities must follow the
    # re-forward's too (over the agreeing prefix)
    lp_diff = [max([abs(a - b[1]) for a, b in zip(lp[:n], r[:n])] or [0.0])
               for lp, r, n in zip(logps, ref, agree)]
    facts["reforward_agreement"] = [
        {"prompt": i, "streamed": len(s), "agree": a,
         "max_logprob_diff": d}
        for i, (s, a, d) in enumerate(zip(streamed, agree, lp_diff))]
    if exact:
        for s, r, a, d in zip(streamed, ref_toks, agree, lp_diff):
            assert a == len(s), \
                f"stream diverges from the re-forward at token {a}: " \
                f"{s[a:a + 4]} vs {r[a:a + 4]}"
            assert d < 5e-2, f"log-probabilities differ by {d}"
    facts["decode_decisions"] = [
        d for d in kreg.decisions_snapshot() if d["op"] == "DecodeAttention"]
    facts["streams"] = [s for s in streamed[:n_checked]]
    return facts


# ---------------------------------------------------------------------------
# end-to-end example (C++ staging, donation under snapshots, predict server)
# ---------------------------------------------------------------------------

def phase_example(steps=12):
    import contextlib
    import runpy

    argv, sys.argv = sys.argv, ["train_mnist_end_to_end.py",
                                "--steps", str(steps)]
    try:
        # the example narrates on stdout; stdout here is JSON lines
        with contextlib.redirect_stdout(sys.stderr):
            runpy.run_path(os.path.join(ROOT, "examples",
                                        "train_mnist_end_to_end.py"),
                           run_name="__main__")
    except SystemExit as e:
        assert not e.code, f"example exited with {e.code}"
    finally:
        sys.argv = argv
    return {"example": "train_mnist_end_to_end.py", "steps": steps}


# ---------------------------------------------------------------------------
# --chips 4: dp=4 train step vs one device; tp=4 decode vs tp=1
# ---------------------------------------------------------------------------

def phase_multichip_dp(cfg, batch, seq_len, n, backend):
    """One BERT train step under Mesh({"dp": n}) with shard_feed, against
    the same global batch and the same initial weights on one device."""
    import contextlib

    import jax

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu import parallel

    def one_step(mesh):
        stf.reset_default_graph()
        stf.set_random_seed(0)
        with mesh if mesh is not None else contextlib.nullcontext():
            m, feed = _bert_graph(cfg, batch, seq_len,
                                  data_parallel=mesh is not None)
            with stf.Session() as sess:
                sess.run(stf.global_variables_initializer())
                tvars = stf.trainable_variables()
                before = {v.name: np.asarray(sess.run(v)) for v in tvars}
                fetches = [m["train_op"], m["loss"]]
                exe = sess.plan(fetches, feeds=list(feed)).compile()
                _, loss = sess.run(fetches, feed_dict=feed)
                devices = {name: sorted(d.id for d in a.sharding.device_set)
                           for name, a in sess._variable_store.values.items()
                           if hasattr(a, "sharding")}
                after = {v.name: np.asarray(sess.run(v)) for v in tvars}
        return float(loss), before, after, devices, exe

    # Both graphs are built with the kernel registry's documented kill
    # switch (mode "off": per-variable optimizer assigns). Under the
    # mesh no Mosaic kernel can be used (GSPMD cannot partition one:
    # registry reason mesh_auto_partitioned), and the XLA lowering of
    # the FUSED flat-group Adam makes the TPU compile of this step take
    # >20 min against 41 s with the per-variable tail (CHANGES.md, PR 21).
    stf.kernels.set_mode("off")
    try:
        loss1, init1, w1, dev1, _ = one_step(None)
        lossn, initn, wn, devn, exe = one_step(parallel.Mesh({"dp": n}))
    finally:
        stf.kernels.set_mode(None)
    assert all(np.array_equal(init1[k], initn[k]) for k in init1), \
        "initial weights differ between the one-device and the dp run"
    hlo = exe._compiled.as_text()
    # Adam's first step moves every weight by lr * g / (|g| + eps): a
    # sign, so where a gradient is all-reduce-order noise around zero
    # the two runs may step opposite ways. 2 * lr bounds the difference,
    # plus one bfloat16 ulp (1.2e-4 below |w| = 0.0625) for the bf16
    # weights; the share that differs by more than lr / 10 is bounded too.
    lr, bf16_ulp = 1e-4, 2.0 ** -13
    diff = {k: np.abs(wn[k].astype(np.float32) - w1[k].astype(np.float32))
            for k in w1}
    worst = float(max(d.max() for d in diff.values()))
    differing = float(sum((d > lr / 10).sum() for d in diff.values())
                      / sum(d.size for d in diff.values()))
    moved = float(max(np.abs(w1[k].astype(np.float32)
                             - init1[k].astype(np.float32)).max()
                      for k in w1))
    all_ids = sorted(d.id for d in jax.devices()[:n])
    facts = {"model": f"bert {cfg.num_layers}x{cfg.hidden_size} "
                      f"vocab {cfg.vocab_size}", "batch": batch,
             "seq_len": seq_len,
             "dp": n, "loss_one_device": loss1, "loss_dp": lossn,
             "n_variables": len(devn),
             "variables_on_all_devices": sum(
                 ids == all_ids for ids in devn.values()),
             "one_device_run_devices": sorted(
                 {i for ids in dev1.values() for i in ids}),
             "all_reduce_in_hlo": hlo.count("all-reduce"),
             "post_step_weights_max_abs_diff": worst,
             "post_step_weights_share_differing": differing,
             "step_moved_weights_by": moved}
    assert abs(loss1 - lossn) <= 1e-2 * abs(loss1), (loss1, lossn)
    assert (moved > 0 and worst <= 2.1 * lr + bf16_ulp
            and differing < 0.05), \
        (moved, worst, differing)
    assert all(ids == all_ids for ids in devn.values()), \
        {k: v for k, v in devn.items() if v != all_ids}
    assert "all-reduce" in hlo, "no all-reduce in the dp step's HLO"
    # feeds: the compiled step takes a 1/n slice of every fed batch on
    # each device; activations: the partitioned program computes on
    # (batch/n, seq, hidden) blocks
    feed_sh = exe._compiled.input_shardings[0][1]
    shards = {}
    for fname, sh in feed_sh.items():
        shape = exe.feed_avals[fname].shape
        shard = sh.shard_shape(shape)
        shards[fname] = {"global": list(shape), "per_device": list(shard),
                         "devices": len(sh.device_set)}
        assert len(sh.device_set) == n and shard[0] * n == shape[0], \
            (fname, shape, shard)
    facts["feed_shards"] = shards
    block = f"[{batch // n},{seq_len},{cfg.hidden_size}]"
    facts["activation_block"] = block
    facts["activation_block_count_in_hlo"] = hlo.count(block)
    whole = hlo.count(f"[{batch},{seq_len},{cfg.hidden_size}]")
    facts["unsharded_block_count_in_hlo"] = whole
    if backend == "tpu":
        # (off the TPU, mode "off" runs the legacy Pallas lowerings
        # interpreted, and GSPMD replicates an interpreted kernel)
        assert hlo.count(block) > 10 * max(whole, 1), \
            f"activations are not batch-sharded: {block} " \
            f"x{hlo.count(block)} vs the whole batch x{whole}"
    return facts


def phase_multichip_tp(cfg, *, page_len, pages_per_seq, max_live,
                       new_tokens, n):
    """tp=n greedy streams identical to tp=1; per-device model+cache
    bytes about 1/n of the one-device figure."""
    import simple_tensorflow_tpu as stf

    kw = dict(page_len=page_len, pages_per_seq=pages_per_seq,
              max_live=max_live, new_tokens=new_tokens, exact=True,
              n_prompts=2, n_checked=2)
    one = phase_server(cfg, stf.float32, model_name="lm_tp1", **kw)
    # the SAME weights: the tp model restores the tp=1 model's
    # checkpoint (on the chip a fresh init under the tp mesh does not
    # reproduce the one-device init from the same seed — CHANGES.md,
    # PR 21 — so two fresh models are two different models)
    tpn = phase_server(cfg, stf.float32, tp=n, model_name=f"lm_tp{n}",
                       checkpoint=one["checkpoint"], **kw)
    assert one["streams"] == tpn["streams"], "tp streams differ from tp=1"
    facts = {"tp": n, "streams_identical": True,
             "tokens_per_stream": [len(s) for s in tpn["streams"]],
             "cache_bytes_total": tpn["cache_bytes_total"],
             "model_bytes_per_device_tp1": one["model_bytes_per_device"],
             f"model_bytes_per_device_tp{n}": tpn["model_bytes_per_device"],
             "prefix_cache_tp": tpn["prefix_cache"],
             "reforward_agreement_tp": tpn["reforward_agreement"]}
    whole = max(one["model_bytes_per_device"])
    if whole:  # the CPU backend reports no memory_stats
        share = [b / whole for b in tpn["model_bytes_per_device"]]
        facts["per_device_share_of_tp1"] = share
        assert len(share) >= n and all(
            0.5 / n < x < 1.6 / n for x in share[:n]), share
    return facts


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    n_dev = len(jax.devices())
    if n_dev < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {n_dev} device(s)",
              file=sys.stderr)
        return 1

    import jaxlib

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.compiler import aot
    from simple_tensorflow_tpu.models import bert
    from simple_tensorflow_tpu.models import transformer as tr

    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_version = "?"
    cache_dir = aot.enable_persistent_cache()

    def cache_stats():
        files = os.listdir(cache_dir) if os.path.isdir(cache_dir) else []
        return {"entries": len(files),
                "bytes": sum(os.path.getsize(os.path.join(cache_dir, f))
                             for f in files)}

    clock = CompileClock()
    emit("start", device_kind=dev.device_kind, device_count=n_dev,
         jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, compile_cache_dir=cache_dir,
         compile_cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"),
         compile_cache_max_size=os.environ.get(
             "JAX_COMPILATION_CACHE_MAX_SIZE"),
         compile_cache_before=cache_stats())

    bert_cfg = bert.BertConfig.base()
    # Transformer-big widths; only the position table is lengthened so
    # the paged cache can hold sequences of 1024 tokens
    lm_cfg = dataclasses.replace(tr.TransformerConfig.big(), max_len=1024)
    batch, seq_len = 24, 512
    lm_kw = dict(page_len=64, pages_per_seq=16, max_live=8, new_tokens=64)

    def run(name, fn, *a, **kw):
        mark = clock.mark()
        facts = fn(*a, **kw)
        emit(name, **facts, **clock.since(mark),
             peak_bytes_in_use=peak_bytes())

    if args.chips == 4:
        run("multichip_tp", phase_multichip_tp, lm_cfg, n=4, **lm_kw)
        run("multichip_dp", phase_multichip_dp, bert_cfg, batch, seq_len, 4,
            "tpu")
    else:
        run("native", phase_native)
        run("trainer", phase_trainer, bert_cfg, batch, seq_len, "tpu")
        run("kernels", phase_kernels, bert_cfg, lm_cfg, batch, seq_len,
            lm_kw["max_live"], lm_kw["page_len"] * lm_kw["pages_per_seq"],
            lm_kw["page_len"])
        # the model's default compute dtype: token-for-token equality is
        # pinned in float32, where a tie cannot flip an argmax between
        # two correct lowerings. (A bf16 server phase ran in this PR's
        # earlier chip runs and agreed 64/64 with the bf16 re-forward —
        # CHANGES.md, PR 21; phase_server takes the dtype.)
        run("server_f32", phase_server, lm_cfg, stf.float32, exact=True,
            model_name="lm_f32", **lm_kw)
        run("example", phase_example)

    emit("end", compile_cache_after=cache_stats(),
         compile_s_total=round(clock.compile_s, 2),
         cache_hits=clock.hits, cache_misses=clock.misses)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
