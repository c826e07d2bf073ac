"""Generative inference engine (ISSUE 12): KV-cache op conformance,
cached-vs-naive beam-search parity, decode-attention kernel parity,
token-level continuous batching (mid-decode join/leave bit-for-bit,
EOS retirement and slot reuse under churn, per-token deadlines), the
int8 decode route, and the serving-decode-cache lint rule."""

import os
import tempfile
import threading
import time

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu import analysis, serving
from simple_tensorflow_tpu.framework import errors, op_registry
from simple_tensorflow_tpu.kernels import registry as kreg
from simple_tensorflow_tpu.models import transformer as tr
from simple_tensorflow_tpu.ops import kv_cache_ops as kvc


@pytest.fixture(autouse=True)
def _fresh_graph():
    stf.reset_default_graph()
    yield
    stf.reset_default_graph()


# ---------------------------------------------------------------------------
# KV-cache op conformance
# ---------------------------------------------------------------------------

class TestKVCacheOps:
    def test_alloc_append_gather_roundtrip(self):
        c = kvc.kv_cache("c_rt", num_slots=4, max_len=8,
                         inner_shape=(2, 3), dtype=stf.float32)
        alloc = c.alloc()
        val = stf.placeholder(stf.float32, [2, 1, 2, 3], "val")
        slots = stf.placeholder(stf.int32, [2], "slots")
        pos = stf.placeholder(stf.int32, [2], "pos")
        gathered = c.append_and_gather(val, slots, pos)
        with stf.Session() as sess:
            sess.run(alloc.op)
            v = np.arange(12, dtype=np.float32).reshape(2, 1, 2, 3)
            g = sess.run(gathered, {val: v,
                                    slots: np.array([1, 3], np.int32),
                                    pos: np.array([0, 5], np.int32)})
            assert g.shape == (2, 8, 2, 3)
            assert np.array_equal(g[0, 0], v[0, 0])
            assert np.array_equal(g[1, 5], v[1, 0])
            assert (g[0, 1:] == 0).all() and (g[1, :5] == 0).all()
            # append is an accumulating in-place update across runs
            g2 = sess.run(gathered, {val: v + 100.0,
                                     slots: np.array([1, 3], np.int32),
                                     pos: np.array([1, 6], np.int32)})
            assert np.array_equal(g2[0, 0], v[0, 0])       # survives
            assert np.array_equal(g2[0, 1], v[0, 0] + 100.0)

    def test_multi_position_prefill_append(self):
        # P > 1: the prefill path writes a whole prompt's rows at once
        c = kvc.kv_cache("c_pf", num_slots=3, max_len=6,
                         inner_shape=(), dtype=stf.float32)
        alloc = c.alloc()
        val = stf.placeholder(stf.float32, [2, 4], "valp")
        slots = stf.placeholder(stf.int32, [2], "slotsp")
        pos = stf.placeholder(stf.int32, [2], "posp")
        gathered = c.append_and_gather(val, slots, pos)
        with stf.Session() as sess:
            sess.run(alloc.op)
            v = np.arange(8, dtype=np.float32).reshape(2, 4)
            g = sess.run(gathered, {val: v,
                                    slots: np.array([2, 0], np.int32),
                                    pos: np.array([0, 2], np.int32)})
            assert np.array_equal(g[0, :4], v[0])
            assert np.array_equal(g[1, 2:6], v[1])
            assert (g[1, :2] == 0).all()

    def test_alloc_resets_slots(self):
        c = kvc.kv_cache("c_reset", num_slots=2, max_len=2,
                         inner_shape=(), dtype=stf.float32)
        alloc = c.alloc()
        val = stf.placeholder(stf.float32, [1, 1], "valr")
        one = stf.constant(np.array([0], np.int32))
        gathered = c.append_and_gather(val, one, one * 0)
        with stf.Session() as sess:
            sess.run(alloc.op)
            sess.run(gathered, {val: np.ones((1, 1), np.float32)})
            sess.run(alloc.op)  # engine reset: back to zeros
            g = sess.run(c.gather(one))
            assert (g == 0).all()

    def test_effects_declared(self):
        # the hazard engine sees cache ops as resource accesses on the
        # SAME selector space as Assign/ReadVariable
        c = kvc.kv_cache("c_eff", 2, 2, (), stf.float32)
        a = c.alloc()
        g = c.gather(stf.constant(np.array([0], np.int32)))
        eff_a = op_registry.get("KVCacheAlloc").effects
        eff_g = op_registry.get("KVCacheGather").effects
        eff_ap = op_registry.get("KVCacheAppend").effects
        assert eff_a.resolved_writes(a.op) == {"var_name=c_eff"}
        assert eff_g.resolved_reads(g.op) == {"var_name=c_eff"}
        assert eff_ap.update == "update"

    def test_gather_before_alloc_fails(self):
        c = kvc.kv_cache("c_uninit", 2, 2, (), stf.float32)
        g = c.gather(stf.constant(np.array([0], np.int32)))
        with stf.Session() as sess:
            with pytest.raises(errors.FailedPreconditionError):
                sess.run(g)


# ---------------------------------------------------------------------------
# DecodeAttention kernel parity
# ---------------------------------------------------------------------------

class TestDecodeAttention:
    def _case(self, B=3, L=8, H=2, D=4, seed=0):
        rng = np.random.RandomState(seed)
        q = rng.randn(B, H, D).astype(np.float32)
        k = rng.randn(B, L, H, D).astype(np.float32)
        v = rng.randn(B, L, H, D).astype(np.float32)
        return q, k, v

    def _reference(self, q, k, v, lengths, bias=None):
        from simple_tensorflow_tpu.ops.pallas import mha_reference

        B, H, D = q.shape
        out = np.zeros_like(q)
        for b in range(B):
            n = int(lengths[b])
            qr = q[b].reshape(1, H, 1, D)
            kr = k[b, :n].transpose(1, 0, 2).reshape(1, H, n, D)
            vr = v[b, :n].transpose(1, 0, 2).reshape(1, H, n, D)
            bb = bias[b:b + 1, :n] if bias is not None else None
            out[b] = np.asarray(mha_reference(qr, kr, vr, bias=bb)
                                )[0, :, 0, :]
        return out

    def test_both_impls_match_reference(self):
        from simple_tensorflow_tpu.ops.pallas.decode_attention import (
            decode_attention, decode_attention_xla)

        q, k, v = self._case()
        lengths = np.array([3, 8, 5], np.int32)
        ref = self._reference(q, k, v, lengths)
        for fn in (decode_attention, decode_attention_xla):
            out = np.asarray(fn(q, k, v, lengths))
            np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_bias_parity(self):
        from simple_tensorflow_tpu.ops.pallas.decode_attention import (
            decode_attention, decode_attention_xla)

        q, k, v = self._case(B=2, L=8)
        bias = np.where(np.arange(8)[None, :] % 3 == 0, 0.0,
                        -1e9).astype(np.float32).repeat(2, 0).reshape(2, 8)
        lengths = np.full(2, 8, np.int32)
        ref = self._reference(q, k, v, lengths, bias=bias)
        for fn in (decode_attention, decode_attention_xla):
            out = np.asarray(fn(q, k, v, lengths, bias=bias))
            np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_graph_op_force_routed(self):
        # acceptance: the registry reports the decode kernel as routed
        # under force (interpret mode on this CPU mesh)
        q, k, v = self._case()
        lengths = np.array([3, 8, 5], np.int32)
        qp = stf.placeholder(stf.float32, [3, 2, 4], "q")
        kp = stf.placeholder(stf.float32, [3, 8, 2, 4], "k")
        vp = stf.placeholder(stf.float32, [3, 8, 2, 4], "v")
        lp = stf.placeholder(stf.int32, [3], "len")
        out_t = stf.nn.decode_attention(qp, kp, vp, lp)
        before = {r["op"]: r for r in kreg.decisions_snapshot()}
        kreg.set_mode("force")
        try:
            kreg.clear_decisions()
            with stf.Session() as sess:
                out = sess.run(out_t, {qp: q, kp: k, vp: v, lp: lengths})
            np.testing.assert_allclose(
                out, self._reference(q, k, v, lengths), atol=1e-5)
            routed = [r for r in kreg.decisions_snapshot()
                      if r["op"] == "DecodeAttention"]
            assert routed and routed[0]["impl"] == "pallas"
            # offline report agrees (graph_lint --kernels path)
            rep = kreg.routing_report([out_t.op], mode="force")
            assert rep[0]["verdict"] == "routed"
        finally:
            kreg.set_mode(None)
            kreg.clear_decisions()

    def test_auto_mode_falls_back_off_tpu(self):
        q, k, v = self._case()
        impl, reason = kreg.decide(
            "DecodeAttention",
            kreg.aval_key(q, k, v, None, has_bias=False), mode="auto",
            count=False)
        assert (impl, reason) == ("xla", "interpret_backend")


# ---------------------------------------------------------------------------
# Cached beam search == naive re-forward search
# ---------------------------------------------------------------------------

class TestCachedBeamParity:
    def test_token_for_token_and_scores(self):
        cfg = tr.TransformerConfig.tiny()
        src = stf.placeholder(stf.int32, [2, 8], "src")
        ids_n, sc_n = tr.beam_search_decode(
            src, cfg, beam_size=3, decode_len=8,
            compute_dtype=stf.float32)
        ids_c, sc_c = tr.beam_search_decode(
            src, cfg, beam_size=3, decode_len=8,
            compute_dtype=stf.float32, use_cache=True)
        batch = tr.synthetic_wmt_batch(2, 8, 8,
                                       vocab_size=cfg.vocab_size)
        # pad a few source positions: the cross-attention bias must ride
        # the cache path identically
        src_ids = batch["src_ids"].copy()
        src_ids[:, -2:] = cfg.pad_id
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            a_ids, a_sc, b_ids, b_sc = sess.run(
                [ids_n, sc_n, ids_c, sc_c], {src: src_ids})
        # int-exact ids, tight-tolerance scores (ISSUE 12 acceptance)
        assert np.array_equal(a_ids, b_ids)
        np.testing.assert_allclose(a_sc, b_sc, atol=1e-4)

    def test_bf16_compute_dtype_runs(self):
        cfg = tr.TransformerConfig.tiny()
        src = stf.placeholder(stf.int32, [1, 8], "src")
        ids, scores = tr.beam_search_decode(
            src, cfg, beam_size=2, decode_len=6,
            compute_dtype=stf.bfloat16, use_cache=True)
        batch = tr.synthetic_wmt_batch(1, 8, 8,
                                       vocab_size=cfg.vocab_size)
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            out_ids, out_sc = sess.run([ids, scores],
                                       {src: batch["src_ids"]})
        assert out_ids.shape == (1, 2, 6)
        assert (out_ids[:, :, 0] == cfg.eos_id).all()
        assert np.isfinite(out_sc).all()


# ---------------------------------------------------------------------------
# Serving decode program: greedy parity through a checkpoint
# ---------------------------------------------------------------------------

def _naive_greedy(sess, handles, src_row, steps, cfg):
    """Greedy re-forward decode: full decode() per emitted token."""
    seq = np.full((1, handles["L"]), cfg.pad_id, np.int32)
    seq[0, 0] = cfg.eos_id
    out = []
    for t in range(steps):
        logits = sess.run(handles["logits"],
                          {handles["src"]: src_row[None, :],
                           handles["tgt"]: seq})
        tok = int(np.argmax(logits[0, t]))
        out.append(tok)
        if t + 1 < handles["L"]:
            seq[0, t + 1] = tok
    return out


class TestServingDecodeParity:
    def test_greedy_matches_naive_reforward_via_checkpoint(self):
        cfg = tr.TransformerConfig.tiny()
        src_len, L = 8, 8
        tmp = tempfile.mkdtemp(prefix="stf_gen_ckpt_")
        ckpt = os.path.join(tmp, "model")
        g1 = stf.Graph()
        with g1.as_default():
            stf.set_random_seed(7)
            src = stf.placeholder(stf.int32, [1, src_len], "src")
            tgt = stf.placeholder(stf.int32, [1, L], "tgt")
            enc_out, enc_bias = tr.encode(src, cfg, training=False,
                                          compute_dtype=stf.float32)
            logits = tr.decode(tgt, enc_out, enc_bias, cfg,
                               training=False,
                               compute_dtype=stf.float32)
            with stf.Session(graph=g1) as sess:
                sess.run(stf.global_variables_initializer())
                saver = stf.train.Saver()
                saver.save(sess, ckpt)
                batch = tr.synthetic_wmt_batch(
                    1, src_len, L, vocab_size=cfg.vocab_size)
                src_row = batch["src_ids"][0].copy()
                src_row[-2:] = cfg.pad_id  # exercise the bias cache
                naive = _naive_greedy(
                    sess, {"src": src, "tgt": tgt, "logits": logits,
                           "L": L}, src_row, steps=L - 1, cfg=cfg)
        model = tr.TransformerGenerativeModel(
            cfg, src_len, num_slots=2, max_decode_len=L,
            checkpoint=ckpt, aot_warmup=False)
        try:
            model.prefill(src_row[None, :], [0])
            tok = np.array([cfg.eos_id], np.int32)
            cached = []
            for t in range(L - 1):
                nxt, _lp, _b = model.decode(tok, [t], [0])
                cached.append(int(nxt[0]))
                tok = nxt
        finally:
            model.close()
        assert cached == naive

    def test_int8_decode_path(self):
        cfg = tr.TransformerConfig.tiny()
        model = tr.TransformerGenerativeModel(
            cfg, 8, num_slots=2, max_decode_len=6, init_fresh=True,
            int8=True, aot_warmup=False)
        try:
            batch = tr.synthetic_wmt_batch(1, 8, 8,
                                           vocab_size=cfg.vocab_size)
            model.prefill(batch["src_ids"], [0])
            tok = np.array([cfg.eos_id], np.int32)
            toks = []
            for t in range(4):
                nxt, lp, _b = model.decode(tok, [t], [0])
                toks.append(int(nxt[0]))
                tok = nxt
            assert all(0 <= t < cfg.vocab_size for t in toks)
        finally:
            model.close()

    def test_int8_force_routes_quant_matmul(self):
        cfg = tr.TransformerConfig.tiny()
        kreg.set_mode("force")
        try:
            kreg.clear_decisions()
            model = tr.TransformerGenerativeModel(
                cfg, 8, num_slots=2, max_decode_len=6, init_fresh=True,
                int8=True, aot_warmup=False)
            try:
                batch = tr.synthetic_wmt_batch(
                    1, 8, 8, vocab_size=cfg.vocab_size)
                model.prefill(batch["src_ids"], [0])
                model.decode([cfg.eos_id], [0], [0])
            finally:
                model.close()
            decided = {r["op"]: r["impl"]
                       for r in kreg.decisions_snapshot()}
            assert decided.get("DecodeAttention") == "pallas"
            assert decided.get("QuantMatMul") == "pallas"
        finally:
            kreg.set_mode(None)
            kreg.clear_decisions()


# ---------------------------------------------------------------------------
# Token-level continuous batching: the engine
# ---------------------------------------------------------------------------

class _FakeModel:
    """Deterministic duck-typed model: sequence for slot s emits tokens
    100+s repeatedly and EOS after ``eos_after[prompt_id]`` tokens.
    Decode is independent per row — like the real decode program."""

    eos_id = 1
    pad_id = 0
    src_len = 4
    num_slots = 4
    max_decode_len = 16

    def __init__(self, eos_after, delay_s=0.0):
        self.eos_after = dict(eos_after)   # prompt id -> #tokens pre-EOS
        self.delay_s = delay_s
        self.prompt_of_slot = {}
        self.emitted = {}
        self.prefills = 0
        self.decode_calls = []
        self.closed = False

    def prefill(self, src_rows, slots):
        self.prefills += 1
        for row, slot in zip(np.asarray(src_rows), np.asarray(slots)):
            pid = int(row[0])
            self.prompt_of_slot[int(slot)] = pid
            self.emitted[int(slot)] = 0

    def decode(self, tokens, positions, slots):
        if self.delay_s:
            time.sleep(self.delay_s)
        n = len(slots)
        bucket = 1
        while bucket < n:
            bucket *= 2
        bucket = min(bucket, self.num_slots)
        self.decode_calls.append((n, bucket))
        out, lps = [], []
        for slot in np.asarray(slots):
            slot = int(slot)
            pid = self.prompt_of_slot[slot]
            self.emitted[slot] += 1
            if self.emitted[slot] > self.eos_after.get(pid, 10 ** 9):
                out.append(self.eos_id)
            else:
                out.append(100 + pid)
            lps.append(-0.5)
        return np.asarray(out, np.int32), np.asarray(lps, np.float32), \
            bucket

    def close(self):
        self.closed = True


def _prompt(pid):
    return np.array([pid, 0, 0, 0], np.int32)


class TestGenerativeEngine:
    def test_eos_retirement_and_slot_reuse_under_churn(self):
        fake = _FakeModel({i: (i % 5) + 1 for i in range(12)})
        pol = serving.DecodePolicy(num_slots=4, max_decode_len=16,
                                   max_new_tokens=12)
        with serving.GenerativeEngine("churn", fake, pol) as eng:
            futs = [eng.generate(_prompt(i)) for i in range(12)]
            results = [f.result(timeout=30) for f in futs]
        for i, r in enumerate(results):
            want = (i % 5) + 1
            assert r["outcome"] == "eos"
            assert list(r["tokens"]) == [100 + i] * want + [fake.eos_id]
        assert fake.closed
        # slots were REUSED: 12 sequences over 4 slots
        assert len({s for s in fake.prompt_of_slot}) <= 4
        # churn kept fill high: most steps ran multiple live sequences
        fills = [n / b for n, b in fake.decode_calls]
        assert sum(fills) / len(fills) > 0.5

    def test_join_leave_bitexact_vs_solo(self):
        cfg = tr.TransformerConfig.tiny()
        model = tr.TransformerGenerativeModel(
            cfg, 8, num_slots=4, max_decode_len=8,
            decode_bucket_sizes=[4], init_fresh=True, aot_warmup=False)
        pol = serving.DecodePolicy(num_slots=4, max_decode_len=8,
                                   bucket_sizes=[4], max_new_tokens=6)
        batch = tr.synthetic_wmt_batch(4, 8, 8,
                                       vocab_size=cfg.vocab_size)
        with serving.GenerativeEngine("bitexact", model, pol) as eng:
            # solo: one at a time through the SAME bucket-4 program
            solo = []
            for i in range(4):
                r = eng.generate(batch["src_ids"][i],
                                 max_new_tokens=4 + i % 3
                                 ).result(timeout=60)
                solo.append(list(r["tokens"]))
            # churning: all four at once, staggered budgets so they
            # LEAVE at different steps (and later ones decode in a
            # partially-filled batch)
            futs = [eng.generate(batch["src_ids"][i],
                                 max_new_tokens=4 + i % 3)
                    for i in range(4)]
            joined = [list(f.result(timeout=60)["tokens"]) for f in futs]
        assert joined == solo

    def test_per_token_deadline_no_batch_stall(self):
        fake = _FakeModel({0: 100, 1: 2}, delay_s=0.02)
        pol = serving.DecodePolicy(num_slots=2, max_decode_len=16,
                                   max_new_tokens=50)
        with serving.GenerativeEngine("deadline", fake, pol) as eng:
            slow = eng.generate(_prompt(0), timeout_ms=120)
            fast = eng.generate(_prompt(1))
            r_fast = fast.result(timeout=30)
            assert r_fast["outcome"] == "eos"
            with pytest.raises(errors.DeadlineExceededError):
                slow.result(timeout=30)
            # the expired request emitted SOME tokens before retiring
            # mid-decode (per-token deadline, not per-request)
            assert slow.exception() is not None

    def test_streaming_and_queue_backpressure(self):
        fake = _FakeModel({i: 3 for i in range(6)})
        pol = serving.DecodePolicy(num_slots=2, max_decode_len=16)
        tokens_seen = []
        with serving.GenerativeEngine("stream", fake, pol) as eng:
            futs = [eng.generate(
                _prompt(i),
                on_token=(lambda t, lp: tokens_seen.append(t))
                if i == 0 else None) for i in range(6)]
            results = [f.result(timeout=30) for f in futs]
        assert all(r["outcome"] == "eos" for r in results)
        assert tokens_seen == list(results[0]["tokens"])

    def test_close_rejects_new_drains_queued(self):
        fake = _FakeModel({i: 2 for i in range(3)})
        pol = serving.DecodePolicy(num_slots=2, max_decode_len=16)
        eng = serving.GenerativeEngine("drain", fake, pol)
        futs = [eng.generate(_prompt(i)) for i in range(3)]
        eng.close()
        for f in futs:
            assert f.result(timeout=30)["outcome"] == "eos"
        late = eng.generate(_prompt(0))
        with pytest.raises(errors.UnavailableError):
            late.result(timeout=5)

    def test_prompt_too_long_rejected(self):
        fake = _FakeModel({})
        pol = serving.DecodePolicy(num_slots=2, max_decode_len=16)
        with serving.GenerativeEngine("toolong", fake, pol) as eng:
            fut = eng.generate(np.zeros(99, np.int32))
            with pytest.raises(errors.InvalidArgumentError):
                fut.result(timeout=5)

    def test_decode_metrics_populated(self):
        from simple_tensorflow_tpu.platform import monitoring

        fake = _FakeModel({i: 2 for i in range(4)})
        pol = serving.DecodePolicy(num_slots=4, max_decode_len=16)
        with serving.GenerativeEngine("metrics_eng", fake, pol) as eng:
            futs = [eng.generate(_prompt(i)) for i in range(4)]
            [f.result(timeout=30) for f in futs]
        exported = monitoring.export()
        toks = exported["/stf/serving/decode_tokens"]["cells"]
        assert any("metrics_eng" in str(k) and v >= 4
                   for k, v in toks.items())
        seqs = exported["/stf/serving/decode_sequences"]["cells"]
        assert any("metrics_eng" in str(k) and "eos" in str(k) and v == 4
                   for k, v in seqs.items())
        assert "/stf/serving/decode_fill" in exported
        assert "/stf/serving/decode_step_seconds" in exported


class TestReviewRegressions:
    def test_decode_len_beyond_pos_table_raises(self):
        # the position-encoding gather would silently CLAMP past
        # cfg.max_len (wrong tokens, no error) — both cached surfaces
        # must refuse up front
        cfg = tr.TransformerConfig.tiny()  # max_len=32
        src = stf.placeholder(stf.int32, [1, 8], "src")
        with pytest.raises(ValueError, match="max_len"):
            tr.beam_search_decode(src, cfg, decode_len=cfg.max_len + 1,
                                  use_cache=True)
        with pytest.raises(ValueError, match="max_len"):
            tr.build_generative_program(cfg, 8, num_slots=2,
                                        max_decode_len=cfg.max_len + 1)

    def test_zero_and_negative_max_new_tokens(self):
        fake = _FakeModel({0: 5})
        pol = serving.DecodePolicy(num_slots=2, max_decode_len=16)
        with serving.GenerativeEngine("budget0", fake, pol) as eng:
            r = eng.generate(_prompt(0), max_new_tokens=0).result(5)
            assert r["outcome"] == "length" and len(r["tokens"]) == 0
            neg = eng.generate(_prompt(0), max_new_tokens=-1)
            with pytest.raises(errors.InvalidArgumentError):
                neg.result(5)

    def test_policy_bucket_mismatch_rejected(self):
        cfg = tr.TransformerConfig.tiny()
        model = tr.TransformerGenerativeModel(
            cfg, 8, num_slots=4, max_decode_len=6,
            decode_bucket_sizes=[4], init_fresh=True, aot_warmup=False)
        try:
            with pytest.raises(ValueError, match="decode plan"):
                serving.GenerativeEngine(
                    "mismatch", model,
                    serving.DecodePolicy(num_slots=4, max_decode_len=6,
                                         bucket_sizes=[1, 4]))
        finally:
            model.close()

    def test_load_generative_failure_closes_factory_model(self):
        fake = _FakeModel({})
        with serving.ModelServer() as server:
            with pytest.raises(ValueError):
                # policy asks for more slots than the model has: the
                # engine ctor raises AFTER the factory built the model
                server.load_generative(
                    lambda: fake, "leaky",
                    policy=serving.DecodePolicy(num_slots=99,
                                                max_decode_len=16))
        assert fake.closed


class TestModelServerGenerative:
    def test_load_generate_unload(self):
        cfg = tr.TransformerConfig.tiny()
        model = tr.TransformerGenerativeModel(
            cfg, 8, num_slots=2, max_decode_len=6, init_fresh=True,
            aot_warmup=False)
        pol = serving.DecodePolicy(num_slots=2, max_decode_len=6,
                                   max_new_tokens=4)
        batch = tr.synthetic_wmt_batch(2, 8, 8,
                                       vocab_size=cfg.vocab_size)
        with serving.ModelServer() as server:
            server.load_generative(model, "gen", policy=pol)
            assert "gen" in server.model_names
            fut = server.generate(batch["src_ids"][0], model="gen")
            r = fut.result(timeout=60)
            assert len(r["tokens"]) == 4
            rows = server.statusz_info()
            assert any(row.get("kind") == "generative" for row in rows)
            with pytest.raises(errors.AlreadyExistsError):
                server.load_generative(model, "gen")
            server.unload("gen")
            assert "gen" not in server.model_names
            with pytest.raises(errors.NotFoundError):
                server.generate(batch["src_ids"][0], model="gen")


# ---------------------------------------------------------------------------
# lint/serving-decode-cache
# ---------------------------------------------------------------------------

class TestDecodeCacheLint:
    RULE = ["lint/serving-decode-cache"]

    def test_clean_decode_graph_passes(self):
        c = kvc.kv_cache("lc1", 2, 4, (2,), stf.float32)
        c.alloc()
        g = c.gather(stf.placeholder(stf.int32, [1], "s"))
        _ = stf.reduce_sum(g)
        assert not analysis.lint_graph(purpose="serving",
                                       rules=self.RULE)

    def test_missing_committed_sharding_is_error(self):
        g = stf.get_default_graph()
        g.create_op(
            "KVCacheAlloc", [],
            attrs={"var_name": "x", "shape": [2, 4],
                   "dtype": "float32", kvc.CACHE_ATTR: True},
            name="bad_alloc",
            output_specs=[(stf.TensorShape([2, 4]), stf.float32)])
        diags = analysis.lint_graph(purpose="serving", rules=self.RULE)
        assert diags and diags[0].severity == "error"
        assert "committed sharding" in diags[0].message

    def test_cache_host_sink_is_error(self):
        c = kvc.kv_cache("lc2", 2, 4, (2,), stf.float32)
        g = c.gather(stf.placeholder(stf.int32, [1], "s2"))
        stf.Print(g, [g], "cache:")
        diags = analysis.lint_graph(purpose="serving", rules=self.RULE)
        assert any("host-sink" in d.message for d in diags)

    def test_fetched_cache_tensor_is_error(self):
        c = kvc.kv_cache("lc3", 2, 4, (2,), stf.float32)
        g = c.gather(stf.placeholder(stf.int32, [1], "s3"))
        diags = analysis.lint_graph(purpose="serving", fetches=[g],
                                    rules=self.RULE)
        assert any("fetched" in d.message for d in diags)

    def test_gated_off_outside_serving_purpose(self):
        g = stf.get_default_graph()
        g.create_op(
            "KVCacheAlloc", [],
            attrs={"var_name": "y", "shape": [2], "dtype": "float32"},
            name="ungated",
            output_specs=[(stf.TensorShape([2]), stf.float32)])
        assert not analysis.lint_graph(rules=self.RULE)
