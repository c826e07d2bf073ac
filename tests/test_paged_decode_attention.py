"""Decode attention that reads the paged pool through the page table
(PR 30): the kernel against the composed lowering over the gathered
logical view, the graph op that carries it (effects, hazards, lint,
cost model, sharding rule), the counter of the table's live share, and
the paged causal LM served through it token for token.

Interpret mode on the CPU; ``tests/test_tpu_aot_compile.py`` holds the
same kernel, and the real lm-big programs, to the chip's own compiler.
"""

import importlib
import json

import numpy as np
import pytest

import jax.numpy as jnp

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu import analysis, serving
from simple_tensorflow_tpu.framework import (cost_model, errors, graph_io,
                                             op_registry)
from simple_tensorflow_tpu.kernels import registry as kreg
from simple_tensorflow_tpu.models import causal_lm as clm
from simple_tensorflow_tpu.models import transformer as tr
from simple_tensorflow_tpu.ops import kv_cache_ops as kvc
from simple_tensorflow_tpu.platform import monitoring
from simple_tensorflow_tpu.tools import graph_lint

da = importlib.import_module(
    "simple_tensorflow_tpu.ops.pallas.decode_attention")


@pytest.fixture(autouse=True)
def _fresh_graph():
    stf.reset_default_graph()
    yield
    stf.reset_default_graph()
    kreg.set_mode(None)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _pools_and_tables(kq, heads, head_dim, page_len, n_blocks, lengths,
                      seed):
    """Two pools whose last pages are a page of NaNs and the scratch
    page; tables whose rows SHARE the physical pages of their first two
    entries, hold their own pages after them and end in NaN pages (the
    kernel's operand) or the scratch page (the reference's)."""
    rng = np.random.default_rng(seed)
    n_pages = 2 + len(lengths) * n_blocks
    shape = (n_pages + 2, page_len, heads * head_dim)
    k_pool = rng.standard_normal(shape).astype(np.float32)
    v_pool = rng.standard_normal(shape).astype(np.float32)
    nan_page, scratch = n_pages, n_pages + 1
    for pool in (k_pool, v_pool):
        pool[nan_page] = np.nan
        pool[scratch] = 0.0
    tables = np.full((len(lengths), n_blocks), nan_page, np.int32)
    for r, n in enumerate(lengths):
        live = -(-(n + (kq if kq > 1 else 0)) // page_len)
        tables[r, :live] = [p if p < 2 else 2 + r * n_blocks + p
                            for p in range(live)]
    ref_tables = np.where(tables == nan_page, scratch, tables)
    q = rng.standard_normal((len(lengths), kq, heads, head_dim))
    return q.astype(np.float32), k_pool, v_pool, tables, ref_tables


@pytest.mark.parametrize("heads,head_dim", [
    (4, 8),        # every head in one group of 32 lanes
    (16, 64),      # lm-big's heads: all 16 (Kq 1) or fours (a block)
    (2, 128),      # a head is a whole lane tile: no block-diagonal
    (3, 40),       # lanes of a head are no whole tiles: one group
], ids=["h4d8", "h16d64", "h2d128", "h3d40"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block", [False, True], ids=["q1", "qblock"])
def test_paged_kernel_matches_the_composition_over_the_view(
        block, dtype, tol, heads, head_dim):
    page_len, n_blocks = 8, 4
    kq = page_len if block else 1
    if block:
        # a page-aligned block after 0..3 committed pages (causal_offset)
        lengths = [0, page_len, 2 * page_len, (n_blocks - 1) * page_len, 0]
    else:
        lengths = [0, 1, page_len - 1, page_len, page_len + 1,
                   n_blocks * page_len, 0]
    q, k_pool, v_pool, tables, ref_tables = _pools_and_tables(
        kq, heads, head_dim, page_len, n_blocks, lengths,
        seed=heads + block)
    # the last row is a bucket's padding row: every entry the scratch page
    tables[-1] = ref_tables[-1] = k_pool.shape[0] - 1
    q = jnp.asarray(q if block else q[:, 0], dtype)
    k_pool, v_pool = jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    out = da.paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                    causal_offset=block)
    ref = da.paged_decode_attention_xla(q, k_pool, v_pool, ref_tables,
                                        lens, causal_offset=block)
    assert out.shape == q.shape and out.dtype == q.dtype
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    # nothing past a row's horizon reaches the result: not the NaN
    # pages in dead entries, not for rows of length 0
    assert np.isfinite(out).all()
    # a row that sees no position at all (length 0, single query) is 0
    # here and a uniform average over masked keys in the composition:
    # neither is served, the engine's rows always see their own token
    seen = [r for r, n in enumerate(lengths) if block or n > 0]
    np.testing.assert_allclose(out[seen], ref[seen], atol=tol, rtol=tol)


def test_heads_per_group_rule():
    # lm-big, 16 heads x 64: all the heads in one (16, 1024) tile for
    # the single query, 4 heads in (256, 256) tiles for a 64-query block
    # (at most four groups: each is a body to lower at every start)
    assert da.paged_heads_per_group(1, 16, 64) == 16
    assert da.paged_heads_per_group(64, 16, 64) == 4
    assert da.paged_heads_per_group(4, 16, 64) == 4     # 16 rows
    # a head of 128 lanes is its own group once a block has rows enough
    assert da.paged_heads_per_group(64, 8, 128) == 2
    assert da.paged_heads_per_group(64, 4, 128) == 1
    assert da.paged_heads_per_group(1, 8, 128) == 8
    # lanes of a head that are no whole tiles: one group of every head
    assert da.paged_heads_per_group(64, 3, 40) == 3
    # a group always divides the heads
    for heads in (1, 2, 3, 6, 12, 16, 32):
        for kq in (1, 8, 64):
            assert heads % da.paged_heads_per_group(kq, heads, 64) == 0


def test_layers_share_one_trace_and_interpret_is_part_of_the_key(
        monkeypatch):
    """A program calls the kernel once a layer with the same shapes: the
    jitted body is traced once (what a model's set-up pays on every
    start is tracing and lowering, compile cache or not), and
    ``use_interpret`` is still asked at every call."""
    from simple_tensorflow_tpu.ops.pallas import common

    q = jnp.zeros((2, 4, 8), jnp.float32)
    pool = jnp.zeros((5, 8, 32), jnp.float32)
    tables = np.zeros((2, 3), np.int32)
    lens = np.ones((2,), np.int32)
    da._paged_call.clear_cache()
    for _ in range(3):
        da.paged_decode_attention(q, pool, pool, tables, lens)
    assert da._paged_call._cache_size() == 1
    asked = []
    monkeypatch.setattr(common, "use_interpret",
                        lambda: asked.append(1) or True)
    da.paged_decode_attention(q, pool, pool, tables, lens)
    assert asked == [1] and da._paged_call._cache_size() == 1


# ---------------------------------------------------------------------------
# the graph op
# ---------------------------------------------------------------------------

HEADS, HEAD_DIM, PAGE_LEN, N_BLOCKS, POOL = 2, 4, 4, 3, 7


def _caches(tag, paged=True, sharding=None):
    return [kvc.kv_cache(f"{tag}_{kind}", POOL, PAGE_LEN, (HEADS, HEAD_DIM),
                         stf.float32, paged=paged, sharding=sharding)
            for kind in "kv"]


def _append_then_attend(tag, ordered=True):
    """One decode position the way ``_PagedCaches`` builds it: K and V
    rows appended into each row's page, then attention over the table
    (under the appends' control dependency, or not)."""
    kc, vc = _caches(tag)
    ph = {
        "q": stf.placeholder(stf.float32, [2, HEADS, HEAD_DIM], f"{tag}_q"),
        "k": stf.placeholder(stf.float32, [2, 1, HEADS, HEAD_DIM],
                             f"{tag}_kn"),
        "v": stf.placeholder(stf.float32, [2, 1, HEADS, HEAD_DIM],
                             f"{tag}_vn"),
        "tables": stf.placeholder(stf.int32, [2, N_BLOCKS], f"{tag}_t"),
        "dst": stf.placeholder(stf.int32, [2], f"{tag}_d"),
        "off": stf.placeholder(stf.int32, [2], f"{tag}_o"),
        "pos": stf.placeholder(stf.int32, [2], f"{tag}_p"),
    }
    appended = [kc.append(ph["k"], ph["dst"], ph["off"]),
                vc.append(ph["v"], ph["dst"], ph["off"])]
    ph["appended"] = [t.op for t in appended]
    deps = ph["appended"] if ordered else []
    with stf.control_dependencies(deps):
        paged = stf.nn.paged_decode_attention(
            ph["q"], kc, vc, ph["tables"], ph["pos"] + 1)
        gathered = stf.nn.decode_attention(
            ph["q"], kc.gather(ph["tables"]), vc.gather(ph["tables"]),
            ph["pos"] + 1)
    return (kc, vc), ph, paged, gathered


def _feed(ph, seed=0):
    rng = np.random.RandomState(seed)
    pos = np.array([5, 9], np.int32)
    tables = np.array([[1, 2, POOL - 1], [1, 3, 4]], np.int32)
    return {
        ph["q"]: rng.randn(2, HEADS, HEAD_DIM).astype(np.float32),
        ph["k"]: rng.randn(2, 1, HEADS, HEAD_DIM).astype(np.float32),
        ph["v"]: rng.randn(2, 1, HEADS, HEAD_DIM).astype(np.float32),
        ph["tables"]: tables,
        ph["dst"]: tables[np.arange(2), pos // PAGE_LEN],
        ph["off"]: pos % PAGE_LEN, ph["pos"]: pos}


@pytest.mark.parametrize("mode,impl", [("auto", "xla"), ("off", "xla"),
                                       ("force", "pallas")])
def test_op_equals_gather_then_decode_attention(mode, impl):
    kreg.set_mode(mode)
    (kc, vc), ph, paged, gathered = _append_then_attend(f"eq_{mode}")
    fill = [c.append(stf.constant(np.random.RandomState(3).randn(
        POOL, PAGE_LEN, HEADS, HEAD_DIM).astype(np.float32)),
        stf.constant(np.arange(POOL, dtype=np.int32)),
        stf.constant(np.zeros(POOL, np.int32))) for c in (kc, vc)]
    before = dict(kreg.snapshot()["routed"]), dict(
        kreg.snapshot()["fallback"])
    with stf.Session() as sess:
        sess.run([kc.alloc().op, vc.alloc().op])
        sess.run([t.op for t in fill])
        got, want = sess.run([paged, gathered], _feed(ph))
    assert got.shape == (2, HEADS, HEAD_DIM)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    snap = kreg.snapshot()
    if impl == "pallas":
        assert snap["routed"].get("PagedDecodeAttention", 0) \
            == before[0].get("PagedDecodeAttention", 0) + 1
    else:
        fallbacks = sum(v for k, v in snap["fallback"].items()
                        if k.startswith("PagedDecodeAttention:"))
        assert fallbacks == sum(
            v for k, v in before[1].items()
            if k.startswith("PagedDecodeAttention:")) + 1


def test_effects_read_both_caches():
    (kc, vc), _, paged, _ = _append_then_attend("eff")
    eff = op_registry.get("PagedDecodeAttention").effects
    assert eff.resolved_reads(paged.op) == {"var_name=eff_k",
                                            "var_name=eff_v"}
    assert not eff.writes
    assert kvc.is_cache_op(paged.op)
    assert kvc.cache_names(paged.op) == ("eff_k", "eff_v")
    assert paged.op.attrs[kvc.CACHE_ATTR] and paged.op.attrs[kvc.PAGED_ATTR]
    assert paged.op.attrs[kvc.SHARDING_ATTR] == "replicated"
    assert paged.shape.as_list() == [2, HEADS, HEAD_DIM]


@pytest.mark.parametrize("ordered", [True, False],
                         ids=["after_the_appends", "unordered"])
def test_hazard_engine_orders_the_read_after_the_appends(ordered):
    (kc, vc), ph, paged, _ = _append_then_attend(f"hz{int(ordered)}",
                                                 ordered=ordered)
    config = stf.ConfigProto(variable_hazard_mode="raise")
    with stf.Session(config=config) as sess:
        sess.run([kc.alloc().op, vc.alloc().op])
        # a decode step holds both, and something consumes the read
        step = [stf.reduce_sum(paged)] + ph["appended"]
        if ordered:
            sess.run(step, _feed(ph))
        else:
            with pytest.raises(errors.InvalidArgumentError,
                               match="hazard"):
                sess.run(step, _feed(ph))


def test_mismatched_caches_are_refused():
    kc, _ = _caches("mm")
    other = kvc.kv_cache("mm_other", POOL, PAGE_LEN, (HEADS, 2 * HEAD_DIM),
                         stf.float32, paged=True)
    q = stf.placeholder(stf.float32, [2, HEADS, HEAD_DIM], "mm_q")
    t = stf.placeholder(stf.int32, [2, N_BLOCKS], "mm_t")
    n = stf.placeholder(stf.int32, [2], "mm_n")
    with pytest.raises(ValueError, match="declared alike"):
        stf.nn.paged_decode_attention(q, kc, other, t, n)
    with pytest.raises(ValueError, match="query block"):
        stf.nn.paged_decode_attention(q, kc, kc, t, n, causal_offset=True)


class TestLint:
    RULE = ["lint/serving-decode-cache"]

    def test_clean_paged_read_passes(self):
        _, _, paged, _ = _append_then_attend("lc")
        _ = stf.reduce_sum(paged)
        assert not analysis.lint_graph(purpose="serving", rules=self.RULE)

    def test_host_sink_downstream_of_shared_pages_is_an_error(self):
        kc, vc = _caches("ls")
        q = stf.placeholder(stf.float32, [2, HEADS, HEAD_DIM], "ls_q")
        t = stf.placeholder(stf.int32, [2, N_BLOCKS], "ls_t")
        n = stf.placeholder(stf.int32, [2], "ls_n")
        h = stf.reduce_sum(stf.nn.paged_decode_attention(q, kc, vc, t, n))
        stf.Print(h, [h], "leak:")
        diags = analysis.lint_graph(purpose="serving", rules=self.RULE)
        assert any("shared-page" in d.message and d.severity == "error"
                   for d in diags)

    def test_missing_sharding_declaration_is_an_error(self):
        _, _, paged, _ = _append_then_attend("lm")
        del paged.op.attrs[kvc.SHARDING_ATTR]
        diags = analysis.lint_graph(purpose="serving", rules=self.RULE)
        assert any("no committed sharding declaration" in d.message
                   and "PagedDecodeAttention" in d.message for d in diags)

    def test_served_programs_lint_clean_and_hold_no_gather(self):
        cfg = tr.TransformerConfig.tiny()
        model = clm.CausalLMGenerativeModel(
            cfg, page_len=4, pages_per_seq=4, num_pages=16, max_live=4,
            prefill_bucket_sizes=(1, 2), aot_warmup=False, init_fresh=True)
        try:
            ops = model.graph.get_operations()
            types = [op.type for op in ops]
            programs = 2 + 2                 # decode buckets + prefill
            assert types.count("PagedDecodeAttention") \
                == programs * cfg.num_layers
            assert "KVCacheGather" not in types
            assert "DecodeAttention" not in types
            with model.graph.as_default():
                diags = analysis.lint_graph(purpose="serving",
                                            rules=self.RULE)
            assert not [d for d in diags if d.severity == "error"]
            report = [r for r in kreg.routing_report(ops, mode="force")
                      if r.get("type") == "PagedDecodeAttention"]
            assert len(report) == programs * cfg.num_layers
            assert {r["verdict"] for r in report} == {"routed"}
            gd = graph_io.graph_to_graphdef(model.graph)
            dec = model._prog["decode"][4]
            fetches = [dec["next_tok"].name, dec["logp"].name]
        finally:
            model.close()
        # the same through graph_lint --serving's entry point: the op and
        # its two cache names survive the GraphDef round trip
        diags, graph, _ = graph_lint.run_lint(
            json.loads(json.dumps(gd)), fetch_names=fetches,
            purpose="serving")
        assert not [d for d in diags
                    if d.code == "lint/serving-decode-cache"], \
            analysis.format_report(diags)
        paged = [op for op in graph.get_operations()
                 if op.type == "PagedDecodeAttention"]
        assert len(paged) == programs * cfg.num_layers
        assert kvc.cache_names(paged[0]) == ("causal_lm_pg/l0_k",
                                             "causal_lm_pg/l0_v")
        # a scoped import renames the store entries of every op alike
        stf.reset_default_graph()
        graph_io.import_graph_def(gd, name="served")
        scoped = {op.type: op for op in
                  stf.get_default_graph().get_operations()
                  if op.type in ("PagedDecodeAttention", "KVCacheAppend")
                  and "l0_" in str(op.attrs["var_name"])}
        assert scoped["KVCacheAppend"].attrs["var_name"] in \
            kvc.cache_names(scoped["PagedDecodeAttention"])


def test_cost_model_prices_the_pages_not_a_view():
    _, ph, paged, gathered = _append_then_attend("cm")
    by_type = {}
    for op in stf.get_default_graph().get_operations():
        by_type.setdefault(op.type, []).append(op)
    paged_op, = by_type["PagedDecodeAttention"]
    gathers = by_type["KVCacheGather"]
    view_len = N_BLOCKS * PAGE_LEN
    flops = 4.0 * 2 * HEADS * view_len * HEAD_DIM
    assert cost_model._op_flops(paged_op) == flops
    assert cost_model._op_flops(by_type["DecodeAttention"][0]) == flops
    # one K and one V page a table entry beside q, the tables, the
    # lengths and the output — not the (B, L, H, D) views the gathers
    # write and the gathered-view kernel reads again
    pages = 2.0 * 2 * N_BLOCKS * PAGE_LEN * HEADS * HEAD_DIM * 4
    small = 4.0 * (2 * 2 * HEADS * HEAD_DIM + 2 * N_BLOCKS + 2)
    assert cost_model._op_bytes_dispatch(paged_op) == pages + small
    composed = sum(cost_model._op_bytes_dispatch(op) for op in gathers) \
        + cost_model._op_bytes_dispatch(by_type["DecodeAttention"][0])
    assert composed > 1.9 * cost_model._op_bytes_dispatch(paged_op)


def test_sharding_rule_reads_head_sharded_pools_per_shard():
    from simple_tensorflow_tpu.analysis import sharding as shard

    kc, vc = _caches("sh", sharding="tp:heads")
    q = stf.placeholder(stf.float32, [2, HEADS, HEAD_DIM], "sh_q")
    t = stf.placeholder(stf.int32, [2, N_BLOCKS], "sh_t")
    n = stf.placeholder(stf.int32, [2], "sh_n")
    out = stf.nn.paged_decode_attention(q, kc, vc, t, n)
    report = shard.analyze_sharding(
        mesh={"tp": 2}, fetches=[out],
        seed_specs={q.op.name: (None, "tp", None)})
    assert report.spec_of(out) == (None, "tp", None)
    assert not report.collective_edges()
    # slot-sharded pools: the pages the tables address change shards
    stf.reset_default_graph()
    kc, vc = _caches("sl", sharding="dp")
    q = stf.placeholder(stf.float32, [2, HEADS, HEAD_DIM], "sl_q")
    t = stf.placeholder(stf.int32, [2, N_BLOCKS], "sl_t")
    n = stf.placeholder(stf.int32, [2], "sl_n")
    out = stf.nn.paged_decode_attention(q, kc, vc, t, n)
    report = shard.analyze_sharding(mesh={"dp": 2}, fetches=[out])
    edge, = report.collective_edges()
    assert edge.kind == "all-gather"
    assert edge.nbytes == 2 * 2 * N_BLOCKS * PAGE_LEN * HEADS * HEAD_DIM \
        * 4 / 2


# ---------------------------------------------------------------------------
# the counter
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = tr.TransformerConfig.tiny()
    model = clm.CausalLMGenerativeModel(
        cfg, page_len=4, pages_per_seq=4, num_pages=16, max_live=4,
        aot_warmup=False, init_fresh=True, seed=5,
        metrics_label="tiny_paged_lm")
    yield model
    model.close()


@pytest.mark.parametrize("positions,share", [
    ([0, 3], 1 / 4),          # rows of one page: 1 / pages_per_seq
    ([15, 15], 1.0),          # full rows
    ([3, 4, 11], (1 + 2 + 3) / 12),
], ids=["one_page", "full", "mixed"])
def test_decode_live_page_share(tiny_model, positions, share):
    cell = monitoring.get_metric(
        "/stf/serving/decode_live_page_share").get_cell("tiny_paged_lm")
    before = cell.value()
    n = len(positions)
    tables = np.arange(n * 4, dtype=np.int32).reshape(n, 4)
    tiny_model.decode([5] * n, positions, tables)
    after = cell.value()
    assert after["count"] == before["count"] + 1
    assert after["sum"] - before["sum"] == pytest.approx(share)
    assert 0 < tiny_model.statusz_info()["decode_live_page_share"] <= 1
    exported = monitoring.export()["/stf/serving/decode_live_page_share"]
    assert any("tiny_paged_lm" in str(k) for k in exported["cells"])


# ---------------------------------------------------------------------------
# served end to end: the kernel's tokens are the composition's tokens
# ---------------------------------------------------------------------------

def _serve(mode, prompts, new_tokens):
    """Every prompt through prefill, decode, copy-on-write and eviction
    on a pool too small to keep them all: tokens and log-probabilities."""
    kreg.set_mode(mode)
    cfg = tr.TransformerConfig.tiny()
    model = clm.CausalLMGenerativeModel(
        cfg, page_len=4, pages_per_seq=4, num_pages=12, max_live=3,
        prefill_bucket_sizes=(1, 2), aot_warmup=False, init_fresh=True,
        seed=11, metrics_label=f"e2e_{mode}")
    types = [op.type for op in model.graph.get_operations()]
    assert "PagedDecodeAttention" in types
    pol = serving.DecodePolicy(num_slots=3, max_decode_len=model.max_seq_len,
                               bucket_sizes=[1, 3],
                               max_new_tokens=new_tokens)
    routed = kreg.snapshot()["routed"].get("PagedDecodeAttention", 0)
    with serving.GenerativeEngine(f"e2e_{mode}", model, pol) as eng:
        results = [eng.generate(p, max_new_tokens=new_tokens).result(240)
                   for p in prompts[:2]]
        futs = [eng.generate(p, max_new_tokens=new_tokens)
                for p in prompts[2:]]
        results += [f.result(timeout=240) for f in futs]
        stats = eng.statusz_info()["prefix_cache"]
        drift = eng._prefix.reconcile([])
    model.close()
    routed = kreg.snapshot()["routed"].get("PagedDecodeAttention", 0) \
        - routed
    return results, stats, drift, routed


def test_paged_lm_serves_what_the_gathered_view_served():
    cfg = tr.TransformerConfig.tiny()
    rng = np.random.RandomState(9)
    base = [int(t) for t in rng.randint(2, cfg.vocab_size, 9)]
    prompts = [base,                                  # two full pages
               base[:6] + [int(rng.randint(2, cfg.vocab_size))]]  # CoW
    prompts += [[int(t) for t in rng.randint(2, cfg.vocab_size, 5 + i % 6)]
                for i in range(8)]                    # churn: evictions
    want, stats_x, drift_x, routed_x = _serve("auto", prompts, 4)
    got, stats_p, drift_p, routed_p = _serve("force", prompts, 4)
    assert routed_x == 0 and routed_p > 0   # composition, then the kernel
    assert drift_x == drift_p == 0
    assert stats_p["cow_hits"] == stats_x["cow_hits"] >= 1
    # how many pages a run evicts moves with the engine thread's timing
    assert stats_p["evictions"] > 0 and stats_x["evictions"] > 0
    for p, a, b in zip(prompts, want, got):
        assert list(a["tokens"]) == list(b["tokens"]), p
        assert a["outcome"] == b["outcome"]
        np.testing.assert_allclose(b["logprobs"], a["logprobs"],
                                   atol=1e-5, rtol=1e-5)
