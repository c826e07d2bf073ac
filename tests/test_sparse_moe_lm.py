"""The sparse-attention routed-FFN decoder (models/sparse_moe_lm.py) on
the CPU at tiny widths, in float32, against the benchmark's plain
reference (chipbench/reference/sparse_moe_decoder.py, which imports
nothing of the program): the ops, the paged programs, and the engine."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import simple_tensorflow_tpu as stf  # noqa: E402
from chipbench.reference import sparse_moe_decoder as ref  # noqa: E402
from chipbench.runners import serve_sparse_moe as runner  # noqa: E402
from chipbench.tests import tiny_sparse_moe  # noqa: E402
from simple_tensorflow_tpu import serving  # noqa: E402
from simple_tensorflow_tpu.models import sparse_moe_lm as sm  # noqa: E402
from simple_tensorflow_tpu.ops import kv_cache_ops as kvc  # noqa: E402
from simple_tensorflow_tpu.ops import moe_ops  # noqa: E402
from simple_tensorflow_tpu.ops import sparse_attention_ops as sa  # noqa: E402

PAGE, PAGES_PER_SEQ, SEED = 8, 6, 20270001


def _config():
    """The benchmark's configuration file cut to tiny widths, float32
    (the cut chipbench's own rehearsals use)."""
    config = tiny_sparse_moe.config("float32")
    return config, sm.SparseMoEConfig(**config["program"]["config_kwargs"])


def _model(seed=SEED, **kw):
    config, cfg = _config()
    kw.setdefault("prefill_bucket_sizes", [1, 2])
    model = sm.SparseMoEGenerativeModel(
        cfg, page_len=PAGE, pages_per_seq=PAGES_PER_SEQ, num_pages=30,
        max_live=4, init_fresh=True, seed=0, compute_dtype=stf.float32,
        metrics_label="tiny_sparse_moe", **kw)
    runner.load_weights(model, config, seed)
    return model, config, cfg


@pytest.fixture(scope="module")
def served():
    model, config, cfg = _model()
    yield model, config, cfg
    model.close()


def _prefill(model, prompt, pages, one_call=False):
    """Every page chunk of ``prompt[:-1]`` (the engine's split: the last
    prompt token goes through the first decode step), a ``prefill_chunk``
    a chunk — or with ``one_call`` as the ROWS of one, as the engine's
    admission hands them over: ordered by base, every row under the
    prompt's one table."""
    tables = np.full((1, PAGES_PER_SEQ), model.scratch_page, np.int32)
    tables[0, :len(pages)] = pages
    n = -(-(len(prompt) - 1) // PAGE)
    body = np.full((n * PAGE,), model.pad_id, np.int32)
    body[:len(prompt) - 1] = prompt[:-1]
    body, bases = body.reshape(n, PAGE), PAGE * np.arange(n)
    if one_call:
        model.prefill_chunk(body, bases, np.repeat(tables, n, axis=0),
                            tables[0, :n])
    else:
        for c in range(n):
            model.prefill_chunk(body[c:c + 1], bases[c:c + 1], tables,
                                tables[0, c:c + 1])
    return tables


def _decode_logits(model, tok, pos, tables):
    """One decode position through the bucket-1 program, fetching the
    logits beside what the plan fetches."""
    _, p = model._decode_plans[1]
    feed = {p["tok"]: np.asarray([tok], np.int32),
            p["pos"]: np.asarray([pos], np.int32), p["tables"]: tables,
            p["dst"]: tables[:, pos // PAGE],
            p["off"]: np.asarray([pos % PAGE], np.int32),
            p["slots"]: np.asarray([model.scratch_slot_row], np.int32)}
    logits, nxt = model.session.run([p["logits"], p["next_tok"]], feed)
    return logits[0], int(nxt[0])


@pytest.mark.parametrize("chunks", [3, 5], ids=["one-call", "two-calls"])
def test_a_prompts_chunks_as_rows_equal_one_call_a_chunk(chunks):
    """Rows j = 0..n-1 of one prefill call may be chunks j of ONE prompt:
    a layer appends every row before any attends, so row j reads pages
    0..j as n chained calls do. Bucket 4 holds 3 rows and a pad row; 5
    rows are a call of 4 and a call of 1. Bit-equal decode logits."""
    model, _, cfg = _model(prefill_bucket_sizes=[1, 4], aot_warmup=False)
    rng = np.random.default_rng(chunks)
    prompt = rng.integers(2, cfg.vocab_size,
                          size=PAGE * (chunks - 1) + 4).astype(np.int32)
    chained = _prefill(model, prompt, [3, 7, 11, 2, 9, 5][:chunks + 1])
    rows = _prefill(model, prompt, [14, 4, 21, 8, 1, 17][:chunks + 1],
                    one_call=True)
    tok, pos = int(prompt[-1]), len(prompt) - 1
    want, _ = _decode_logits(model, tok, pos, chained)
    got, _ = _decode_logits(model, tok, pos, rows)
    model.close()
    np.testing.assert_array_equal(got, want)


class TestProgramAgainstReference:
    def test_prefill_then_decode_logits(self, served):
        """Prefill through the paged caches (3 page chunks, the last one
        partial), then 12 decode positions: the logits of every decode
        position against the reference's full forward over prompt +
        served tokens. Contexts run 29..40 with top-k 8, so the indexer's
        selection decides every position. Planting each of: no selection
        (all s <= t attended), no renormalisation of the top-k gates, no
        q/k head norms in the program moved the worst logit by 1.5-2.3 (clean:
        1e-6) and
        failed this test (PR 27, by hand)."""
        model, config, cfg = served
        rng = np.random.default_rng(1)
        prompt = rng.integers(2, cfg.vocab_size, size=29).astype(np.int32)
        tables = _prefill(model, prompt, [3, 7, 11, 2, 9])
        tok, pos, got, toks = int(prompt[-1]), len(prompt) - 1, [], []
        for _ in range(12):
            logits, tok = _decode_logits(model, tok, pos, tables)
            got.append(logits)
            toks.append(tok)
            pos += 1
        spec = config["reference"]["spec"]
        seq = list(prompt) + toks
        want = ref.logits_at(spec, SEED, [seq],
                             [len(prompt) - 1 + np.arange(12)])[0]
        np.testing.assert_allclose(np.stack(got), np.asarray(want),
                                   atol=2e-4, rtol=0)
        assert toks == [int(t) for t in np.argmax(np.asarray(want), -1)]

    def test_reference_rows_and_planted_fault(self, served):
        """What the runner compares: the served tokens' gaps are ~0, the
        float8 control and a planted second-best token are not."""
        model, config, cfg = served
        rng = np.random.default_rng(2)
        prompt = rng.integers(2, cfg.vocab_size, size=20).astype(np.int32)
        tables = _prefill(model, prompt, [4, 5, 6, 8])
        tok, pos, toks, lps = int(prompt[-1]), len(prompt) - 1, [], []
        for _ in range(10):
            nxt, lp, _ = model.decode([tok], [pos], tables)
            tok = int(nxt[0])
            toks.append(tok)
            lps.append(float(lp[0]))
            pos += 1
        spec = config["reference"]["spec"]
        row = ref.served_token_gaps(spec, SEED, [prompt], [toks],
                                    control="fp8")[0]
        assert row["gap"].max() < 1e-4
        assert np.abs(row["logprob"] - np.asarray(lps)).max() < 2e-4
        assert (row["margin"] > 0).all()
        assert np.abs(row["control_logprob"] - row["logprob"]).max() > 0.01
        twisted = list(toks)
        twisted[4] = int(row["second"][4])
        row2 = ref.served_token_gaps(spec, SEED, [prompt], [twisted])[0]
        assert row2["gap"][4] == pytest.approx(row["margin"][4], abs=1e-5)


class TestRoutedFFN:
    def _weights(self, t=24, h=16, e=8, width=8):
        """Uneven routing: feature 0 is positive on every token, and the
        router reads it to shut expert 5 out and to favour expert 2."""
        ks = jax.random.split(jax.random.key(3), 4)
        x = jax.random.normal(ks[0], (t, h))
        x = x.at[:, 0].set(1.0 + jnp.abs(x[:, 0]))
        wr = jax.random.normal(ks[1], (h, e))
        wr = wr.at[:, 5].set(0.0).at[0, 5].set(-100.0).at[0, 2].add(6.0)
        w_gu = 0.3 * jax.random.normal(ks[2], (e, h, 2 * width))
        w_d = 0.3 * jax.random.normal(ks[3], (e, width, h))
        return x, wr, w_gu, w_d

    def test_against_per_expert_loop(self):
        x, wr, w_gu, w_d = self._weights()
        y, counts = moe_ops.routed_ffn(x, wr, w_gu, w_d, top_k=2)
        experts, gates = moe_ops.route(x, wr, top_k=2, norm_topk=True)
        width = w_d.shape[1]
        want = np.zeros(x.shape, np.float32)
        for e in range(w_d.shape[0]):
            h = x @ w_gu[e]
            out = (jax.nn.silu(h[:, :width]) * h[:, width:]) @ w_d[e]
            gate = jnp.sum(jnp.where(experts == e, gates, 0.0), -1)
            want += np.asarray(gate[:, None] * out)
        np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
        counts = np.asarray(counts)
        assert counts.sum() == 2 * x.shape[0]
        assert counts[5] == 0 and counts.max() >= 4 * max(counts.min(), 1)
        np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-6)

    def test_row_mask_leaves_padding_out_of_the_counts(self):
        x, wr, w_gu, w_d = self._weights(t=8)
        mask = jnp.arange(8) < 5
        y_all, _ = moe_ops.routed_ffn(x, wr, w_gu, w_d, top_k=2)
        y, counts = moe_ops.routed_ffn(x, wr, w_gu, w_d, mask, top_k=2)
        assert int(counts.sum()) == 10
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_all))

    def test_gates_not_renormalised_on_request(self):
        x, wr, _, _ = self._weights(t=8)
        _, gates = moe_ops.route(x, wr, top_k=2, norm_topk=False)
        assert float(gates.sum(-1).min()) < 0.95


class TestIndexerSelection:
    def _case(self, seed=4, big_l=24, hi=3, di=4):
        ks = jax.random.split(jax.random.key(seed), 3)
        return (jax.random.normal(ks[0], (2, hi, di)),
                jax.random.normal(ks[1], (2, hi)),
                jax.random.normal(ks[2], (2, big_l, di)))

    def test_causality_and_fewer_than_k(self):
        q_idx, w, k_idx = self._case()
        lengths = jnp.asarray([3, 17], jnp.int32)
        picked, n_valid = sa.indexer_topk(q_idx, w, k_idx, lengths, topk=6)
        assert n_valid.tolist() == [3, 6]
        assert sorted(picked[0, :3].tolist()) == [0, 1, 2]   # all of them
        assert (np.asarray(picked[1]) < 17).all()
        scores = np.asarray(sa.indexer_scores(q_idx, w, k_idx))[1, :17]
        assert set(picked[1].tolist()) == set(np.argsort(-scores)[:6])

    def test_ties_go_to_the_lower_position(self):
        q_idx, w, k_idx = self._case()
        k_idx = k_idx.at[:, 1::2].set(k_idx[:, 0::2])   # 2j+1 equals 2j
        lengths = jnp.asarray([24, 24], jnp.int32)
        picked, _ = sa.indexer_topk(q_idx, w, k_idx, lengths, topk=5)
        for row in np.asarray(picked):
            chosen = set(row.tolist())
            # an odd position is only ever taken with its even twin
            assert all(p - 1 in chosen for p in chosen if p % 2)
            assert sum(p % 2 == 0 for p in chosen) == 3   # 5 = 3 + 2

    @pytest.mark.parametrize("ties", [False, True], ids=["plain", "ties"])
    def test_block_mask_equals_per_position_selection(self, ties):
        """PREFILL's mask (bisection + tie ranks, tile by tile) picks what
        DECODE's top_k picks, position by position."""
        b, s, hq, hkv, d, hi, di, big_l, topk = 2, 8, 4, 2, 8, 3, 4, 40, 6
        ks = jax.random.split(jax.random.key(5), 6)
        k_view = jax.random.normal(ks[0], (b, big_l, hkv, d))
        v_view = jax.random.normal(ks[1], (b, big_l, hkv, d))
        k_idx = jax.random.normal(ks[2], (b, big_l, di))
        if ties:
            k_idx = k_idx.at[:, 1::2].set(k_idx[:, 0::2])
        q = jax.random.normal(ks[3], (b, s, hq, d))
        q_idx = jax.random.normal(ks[4], (b, s, hi, di))
        w = jax.random.normal(ks[5], (b, s, hi))
        base = jnp.asarray([0, 24], jnp.int32)    # row 0 starts under topk
        out = sa.sparse_block_attention(q, q_idx, w, k_view, v_view, k_idx,
                                        base, topk=topk, tile=8)
        for i in range(b):
            for j in range(s):
                t = int(base[i]) + j
                picked, n_valid = sa.indexer_topk(
                    q_idx[i:i + 1, j], w[i:i + 1, j], k_idx[i:i + 1],
                    jnp.asarray([t + 1]), topk=topk)
                one = sa.selected_attention(
                    q[i:i + 1, j], k_view[i:i + 1][:, picked[0]],
                    v_view[i:i + 1][:, picked[0]], n_valid)
                np.testing.assert_allclose(np.asarray(out[i, j]),
                                           np.asarray(one[0]), atol=1e-5)

    def test_reference_mask_breaks_ties_alike(self):
        scores = jnp.asarray([[3.0, 1.0, 3.0, 3.0, 0.5, -jnp.inf],
                              [1.0, 1.0, 1.0, -jnp.inf, -jnp.inf, -jnp.inf]])
        mask = np.asarray(ref.topk_mask(scores, 2))
        assert mask.tolist() == [[True, False, True, False, False, False],
                                 [True, True, False, False, False, False]]
        _, kth = sa.kth_largest_bits(scores, 2)
        assert kth.tolist() == sa._ordered_bits(
            jnp.asarray([3.0, 1.0])).tolist()


def test_gather_rows_reads_through_the_page_table():
    g = stf.Graph()
    with g.as_default():
        cache = kvc.kv_cache("rows_pg", 5, 4, (2, 3), stf.float32,
                             paged=True)
        val = stf.placeholder(stf.float32, [1, 4, 2, 3])
        page = stf.placeholder(stf.int32, [1])
        app = cache.append(val, page, stf.constant(np.zeros(1, np.int32)))
        tables = stf.constant(np.array([[3, 1, 4]], np.int32))
        with stf.control_dependencies([app.op]):
            rows = cache.gather_rows(
                tables, stf.constant(np.array([[5, 0, 7]], np.int32)))
        with stf.Session(graph=g) as sess:
            sess.run(cache.alloc().op)
            data = {p: np.random.RandomState(p).rand(1, 4, 2, 3).astype(
                np.float32) for p in (1, 3)}
            sess.run(app.op, {val: data[3], page: [3]})
            got = sess.run(rows, {val: data[1], page: [1]})
    # logical 5 = page-table block 1 (page 1) row 1; 0 = page 3 row 0
    np.testing.assert_array_equal(got[0, 0], data[1][0, 1])
    np.testing.assert_array_equal(got[0, 1], data[3][0, 0])
    np.testing.assert_array_equal(got[0, 2], data[1][0, 3])
    assert rows.op.type == "KVCacheGatherRows" and kvc.is_cache_op(rows.op)


class TestServed:
    def test_generate_with_cow_over_three_caches(self):
        """ModelServer.generate end to end: B's cached span ends inside
        A's second page, so its tail page is a copy (K, V AND indexer
        keys) of A's; both answers equal the reference's greedy tokens."""
        model, config, cfg = _model(seed=SEED + 1)
        spec = config["reference"]["spec"]
        rng = np.random.default_rng(6)
        base = rng.integers(2, cfg.vocab_size, size=2 * PAGE + 1).tolist()
        prompt_b = base[:PAGE + 3] + [int(rng.integers(2, cfg.vocab_size))]
        server = serving.ModelServer()
        server.load_generative(model, "tiny_sparse_moe",
                               policy=serving.DecodePolicy(
                                   num_slots=4, max_decode_len=model.max_seq_len,
                                   bucket_sizes=model.decode_buckets,
                                   prefill_bucket_sizes=model.prefill_buckets))
        try:
            answers = [server.generate(np.asarray(p, np.int32),
                                       model="tiny_sparse_moe",
                                       max_new_tokens=6).result(timeout=300)
                       for p in (base, prompt_b)]
            row = [r for r in server.statusz_info()
                   if r.get("model") == "tiny_sparse_moe"][0]
        finally:
            server.close()
        assert row["prefix_cache"]["cow_hits"] == 1
        assert row["prefix_cache"]["hit_pages"] >= 1
        for prompt, ans in zip((base, prompt_b), answers):
            toks = [int(t) for t in ans["tokens"]]
            rows = ref.served_token_gaps(spec, SEED + 1, [prompt], [toks])[0]
            assert rows["gap"].max() < 1e-4, (rows["gap"], rows["margin"])
            assert np.abs(rows["logprob"]
                          - np.asarray(ans["logprobs"])).max() < 2e-4

    def test_cow_program_copies_every_cache(self, served):
        model, _, cfg = served
        caches = model._prog["caches"]
        assert len(caches) == cfg.num_layers
        assert all(len(group) == 3 for group in caches)
        copies = [op for op in model.graph.get_operations()
                  if op.type == "KVCachePageCopy"]
        assert len(copies) == 3 * cfg.num_layers
        assert caches[0][2].stored_shape == (31, PAGE, cfg.indexer_head_dim)
        assert caches[0][0].stored_shape == (
            31, PAGE, cfg.num_kv_heads * cfg.head_dim)

    def test_step_counters(self, served):
        from simple_tensorflow_tpu.platform import monitoring

        model, _, cfg = served
        share = monitoring.get_metric(
            "/stf/serving/sparse_selected_share").get_cell("tiny_sparse_moe")
        load = monitoring.get_metric(
            "/stf/serving/moe_load_imbalance").get_cell("tiny_sparse_moe")
        before = share.value()["count"], load.value()["count"]
        tables = np.full((2, PAGES_PER_SEQ), model.scratch_page, np.int32)
        tables[:, :3] = [[1, 2, 3], [4, 5, 6]]
        model.decode([5, 6], [15, 23], tables)
        after = share.value(), load.value()
        assert after[0]["count"] == before[0] + 1
        assert after[1]["count"] == before[1] + 1
        # min(16, 8) + min(24, 8) positions read of 16 + 24
        assert after[0]["max"] >= 16 / 40 - 1e-9
        assert after[1]["min"] >= 1.0

    def test_expert_counts_are_of_the_live_rows(self, served):
        """A live row is one that writes a real page, whatever its token
        id (0 pads a bucket AND is a token the model can emit); the two
        padding rows of the 4-wide bucket are left out."""
        model, _, cfg = served
        tables = np.full((2, PAGES_PER_SEQ), model.scratch_page, np.int32)
        tables[:, :3] = [[1, 2, 3], [4, 5, 6]]
        plan, p = model._decode_plans[4]
        tok, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
        tbl = model._scratch_tables(4)
        tok[:2], pos[:2], tbl[:2] = [model.pad_id, 7], [15, 23], tables
        out = model._run(plan, {
            p["tok"]: tok, p["pos"]: pos, p["tables"]: tbl,
            p["dst"]: tbl[np.arange(4), pos // PAGE], p["off"]: pos % PAGE,
            p["slots"]: np.full(4, model.scratch_slot_row, np.int32)})
        counts = np.asarray(out["expert_counts"])
        assert counts.shape == (cfg.num_layers, cfg.num_experts)
        np.testing.assert_array_equal(
            counts.sum(axis=-1), 2 * cfg.experts_per_token)
