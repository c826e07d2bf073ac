"""Described-topology compiles: every Pallas kernel of the main path,
at the shapes BERT-base and the Transformer-big-width causal LM really
produce, compiled by the TPU's own compiler for a v5e chip that is
DESCRIBED, not attached (on-chip-measurement guide, section 2.3).

Interpret mode hides what Mosaic refuses (the single-query decode
kernel passed every interpret test and failed here until PR 21). These
compile in a second or two each and guard every later PR at no chip
time. Nothing runs: a pass is not a chip run.

All in ONE file (one xdist worker loads libtpu and keeps its lock); the
topology is described inside a module-scoped fixture, never at import.
"""

import contextlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from simple_tensorflow_tpu.kernels import registry as kreg
from simple_tensorflow_tpu.ops import pallas as P
from simple_tensorflow_tpu.ops.pallas import common

BF16, F32 = jnp.bfloat16, jnp.float32

# BERT-base: batch 24, 12 heads x 64, seq 512, hidden 768, vocab 30522,
# 76 masked positions per row. Causal LM at Transformer-big widths:
# 16 heads x 64, d_model 1024, d_ff 4096, 8 live sequences of 1024.
BERT_QKV = (24, 12, 512, 64)
LM_QKV = (8, 16, 512, 64)
BERT_ROWS, BERT_HIDDEN, BERT_VOCAB = 24 * 512, 768, 30522
BERT_PARAMS = 110_000_000
LM_CACHE = (8, 1024, 16, 64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _lowering_for_the_described_chip():
    """Kernels lower natively (the default backend here is the CPU, so
    use_interpret is steered from the test), and the persistent cache
    is off: such an executable cannot be read back without a chip.
    Yields the MonkeyPatch for what else a case has to steer."""
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setattr(common, "use_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield mp
    finally:
        mp.undo()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def tpu_compile(one_chip):
    """compile(fn, *(shape, dtype)) -> compiled HLO text, for the
    described chip."""
    def compile_(fn, *avals):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in avals]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel in the HLO"
        # the name= of the pl.pallas_call names the HLO instruction
        # (wrapped as jvp_<name>_ / transpose_jvp_<name>__ under a
        # gradient): what a profiler trace on the chip shows, and what
        # chipbench's kernel metrics match
        calls = [ln for ln in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        assert calls and all(
            re.match(r"\s*(ROOT )?%[\w.\-]*stf_[a-z0-9_]+(\.\d+)? = ", ln)
            for ln in calls), [ln[:80] for ln in calls]
        return text

    with _lowering_for_the_described_chip():
        yield compile_


def _grad(fn, argnums):
    def loss(*a):
        out = fn(*a)
        out = out[0] if isinstance(out, (tuple, list)) else out
        return jnp.sum(out.astype(F32))
    return jax.grad(loss, argnums)


_SEED = np.asarray([7], np.int32)


@pytest.mark.parametrize("qkv", [BERT_QKV, LM_QKV], ids=["bert", "lm_big"])
class TestFlashAttention:
    def test_forward(self, tpu_compile, qkv):
        tpu_compile(P.flash_attention, *[(qkv, BF16)] * 3)

    def test_forward_bias_dropout(self, tpu_compile, qkv):
        tpu_compile(
            lambda q, k, v, b: P.flash_attention(
                q, k, v, bias=b, dropout_rate=0.1, dropout_seed=_SEED),
            *[(qkv, BF16)] * 3, ((qkv[0], qkv[2]), F32))

    def test_causal_backward(self, tpu_compile, qkv):
        tpu_compile(
            _grad(lambda q, k, v: P.flash_attention(q, k, v, causal=True),
                  (0, 1, 2)),
            *[(qkv, BF16)] * 3)


def test_flash_attention_return_lse_long(tpu_compile):
    tpu_compile(lambda q, k, v: P.flash_attention(q, k, v, return_lse=True),
                *[((1, 16, 2048, 64), BF16)] * 3)


def _kernel_names(text):
    return set(re.findall(r"stf_flash_attention_(?:fwd|bwd_dkv|bwd_dq|bwd)",
                          text))


@pytest.mark.parametrize("what", ["forward", "backward"])
def test_flash_attention_bert_cell_single_pass(tpu_compile, what):
    """What `bert-base.s512` really runs: batch 48, non-causal, a
    (48, 512) float32 key-padding bias. The rule gives one (512, 512)
    tile a head, three heads a step; the backward is ONE call."""
    qkv = (48, 12, 512, 64)

    def attn(q, k, v, b):
        return P.flash_attention(q, k, v, bias=b)

    fn = attn if what == "forward" else (
        lambda q, k, v, b: _grad(lambda q, k, v: attn(q, k, v, b),
                                 (0, 1, 2))(q, k, v))
    took = kreg.metric_flash_tiles.get_cell("single_pass", "512", "512", "3")
    before = took.value()
    text = tpu_compile(fn, *[(qkv, BF16)] * 3, ((48, 512), F32))
    assert took.value() > before, kreg.snapshot()["flash_tiles"]
    assert _kernel_names(text) == {"stf_flash_attention_fwd"} | (
        set() if what == "forward" else {"stf_flash_attention_bwd"})
    # the operand signature chipbench's flash_attn_roofline finds the
    # events by: rank-3 bf16 [B*H, S, D] beside a float32 [B*H, S, 1]
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert all("bf16[576,512,64]" in ln and "f32[576,512,1]" in ln
               for ln in calls), [ln[:200] for ln in calls]


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_attention_streamed_8192(tpu_compile, causal):
    """The long users (ring attention's blocks, return_lse): streamed
    512 x 1024 tiles, forward and the two-call backward."""
    def attn(q, k, v):
        return P.flash_attention(q, k, v, causal=causal, return_lse=True)

    avals = [((1, 16, 8192, 64), BF16)] * 3
    assert _kernel_names(tpu_compile(attn, *avals)) == {
        "stf_flash_attention_fwd"}
    assert _kernel_names(tpu_compile(_grad(attn, (0, 1, 2)), *avals)) == {
        "stf_flash_attention_fwd", "stf_flash_attention_bwd_dkv",
        "stf_flash_attention_bwd_dq"}


@pytest.mark.parametrize("head_dim,dtype,seq", [
    (64, BF16, 128), (64, BF16, 1024), (128, BF16, 1024), (256, BF16, 512),
    (64, F32, 512), (128, F32, 1024), (256, F32, 512), (256, F32, 2048)],
    ids=lambda v: getattr(v, "__name__", str(v)))
def test_flash_attention_rule_fits_vmem(tpu_compile, head_dim, dtype, seq):
    """The tile rule's choice at the corners of its table: Mosaic
    accepts the tiles and the step fits VMEM, forward and backward
    (12 heads, so a whole-head step holds several)."""
    avals = [((2, 12, seq, head_dim), dtype)] * 3
    tpu_compile(_grad(lambda q, k, v: P.flash_attention(q, k, v),
                      (0, 1, 2)), *avals)


def test_layer_norm_forward_backward(tpu_compile):
    avals = [((BERT_ROWS, BERT_HIDDEN), BF16), ((BERT_HIDDEN,), F32),
             ((BERT_HIDDEN,), F32)]
    tpu_compile(P.layer_norm, *avals)
    tpu_compile(_grad(P.layer_norm, (0, 1, 2)), *avals)


def test_softmax_xent_forward_backward(tpu_compile):
    avals = [((24 * 76, BERT_VOCAB), BF16), ((24 * 76,), jnp.int32)]
    tpu_compile(P.softmax_cross_entropy, *avals)
    tpu_compile(_grad(P.softmax_cross_entropy, (0,)), *avals)


def test_fused_adam_flat_group(tpu_compile):
    n = (BERT_PARAMS,)
    tpu_compile(
        lambda p, m, v, g, a: P.adam_update(p, m, v, g, a, beta1=0.9,
                                            beta2=0.999, eps=1e-8),
        (n, F32), (n, F32), (n, F32), (n, F32), ((), F32))


def test_fused_momentum_flat_group(tpu_compile):
    n = (BERT_PARAMS,)
    tpu_compile(P.momentum_update, (n, F32), (n, F32), (n, F32), ((), F32),
                ((), F32))


def test_dropout_bias_residual(tpu_compile):
    x = ((BERT_ROWS, BERT_HIDDEN), BF16)
    tpu_compile(
        lambda x, r, b: P.dropout_bias_residual(x, r, b, rate=0.1,
                                                seed=_SEED),
        x, x, ((BERT_HIDDEN,), BF16))


def test_quant_matmul(tpu_compile):
    tpu_compile(P.quant_matmul, ((512, 1024), BF16), ((1024, 4096), jnp.int8),
                ((4096,), F32))


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
class TestDecodeAttention:
    def test_single_query(self, tpu_compile, dtype):
        # the per-token decode shape: one query per live sequence.
        # Mosaic refused this kernel before PR 21 (a dot_general with no
        # non-contracting lhs dimension)
        b, _, h, d = LM_CACHE
        tpu_compile(P.decode_attention, ((b, h, d), dtype),
                    (LM_CACHE, dtype), (LM_CACHE, dtype), ((b,), jnp.int32))

    def test_query_block(self, tpu_compile, dtype):
        # page-block prefill: 64 query positions, causal inside the block
        b, _, h, d = LM_CACHE
        tpu_compile(
            lambda q, k, v, n: P.decode_attention(q, k, v, n,
                                                  causal_offset=True),
            ((b, 64, h, d), dtype), (LM_CACHE, dtype), (LM_CACHE, dtype),
            ((b,), jnp.int32))


def test_interpret_follows_the_lowering_platform_not_a_cached_answer(
        monkeypatch):
    """use_interpret is asked at every trace: a process that touched a
    kernel while the CPU was the default is not frozen into interpret
    mode, and interpreting on a TPU process is an error."""
    assert common.use_interpret() is True          # CPU default backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert common.use_interpret() is False         # no cached answer
    with jax.default_device(jax.devices("cpu")[0]):
        with pytest.raises(RuntimeError, match="interpret mode"):
            common.use_interpret()


# ---------------------------------------------------------------------------
# The paged KV pool at lm-big's real sizes: a cache append must update
# the donated pool in place. Stored (3073, 64, 16, 64) the pool's minor
# dimension is half a lane tile, the compiler lays the donated parameter
# out pages-minor, and every append pays three pool-sized relayout
# copies (403 MB each; 78 % of lm-big.backlog's device time, PERF.md
# PR 26). Stored lane-dense (ops/kv_cache_ops.stored_shape) there is
# none: this is where "in place" is asserted.
# ---------------------------------------------------------------------------

LM_PAGES, LM_PAGE_LEN, LM_PAGES_PER_SEQ = 3072, 64, 32
LM_POOL = (LM_PAGES + 1, LM_PAGE_LEN, 16, 64)      # + the scratch page
_HLO_INSTR = re.compile(
    r"\s*(?:ROOT )?%[\w.\-]+ = [a-z0-9]+\[([0-9,]*)\]\S* ([\w\-]+)\(")


@pytest.fixture(scope="module")
def lm_big_programs(one_chip):
    """HLO text of the decode (bucket 96) and page-chunk prefill
    (bucket 8) programs ``build_causal_lm_program`` emits at lm-big's
    sizes, lowered from the Session's own donating step function for
    the described chip. Nothing is allocated: state is avals."""
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.kernels import registry as kreg
    from simple_tensorflow_tpu.models import causal_lm
    from simple_tensorflow_tpu.models.transformer import TransformerConfig

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    cfg = TransformerConfig(vocab_size=32768, d_model=1024, num_heads=16,
                            d_ff=4096, num_layers=6, dropout=0.0,
                            max_len=2048, layer_norm_eps=1e-6)
    graph = stf.Graph()
    with _lowering_for_the_described_chip() as mp, graph.as_default(), \
            stf.Session(graph=graph) as sess:
        mp.setattr(kreg, "backend", lambda: "tpu")
        mp.setattr(kreg, "_default_mode", "force")
        prog = causal_lm.build_causal_lm_program(
            cfg, page_len=LM_PAGE_LEN, pages_per_seq=LM_PAGES_PER_SEQ,
            num_pages=LM_PAGES, decode_bucket_sizes=(96,),
            prefill_bucket_sizes=(8,), compute_dtype=stf.bfloat16)
        caches = [c for pair in prog["caches"] for c in pair]
        assert all(c.shape == LM_POOL for c in caches)
        state = {v.var_name: aval(v.shape.as_list(),
                                  v.dtype.base_dtype.np_dtype)
                 for v in stf.global_variables()}
        state.update({c.name: aval(c.stored_shape, c.dtype.np_dtype)
                      for c in caches + [prog["last_tok"]]})

        def text(fetches, feeds):
            step = sess.plan(fetches, feeds=feeds)._step
            feed_avals = {
                t.name: aval(t.shape.as_list(), t.dtype.base_dtype.np_dtype)
                for t in step.feed_tensors}
            return step.jitted.lower(
                dict(state), feed_avals, aval((), jax.random.key(0).dtype),
                aval((), np.uint32)).compile().as_text()

        d, p = prog["decode"][96], prog["prefill"][8]
        texts = {
            "decode96": text(
                {"next_tok": d["next_tok"], "logp": d["logp"]},
                [d["tok"], d["pos"], d["tables"], d["dst"], d["off"],
                 d["slots"]]),
            "prefill8": text(
                {"done": p["op"]},
                [p["tok"], p["base"], p["tables"], p["dst"]]),
        }
    return {"texts": texts, "n_state": len(state), "n_pools": len(caches)}


@pytest.mark.parametrize("program", ["decode96", "prefill8"])
def test_lm_big_cache_append_updates_the_pool_in_place(lm_big_programs,
                                                       program):
    text = lm_big_programs["texts"][program]
    n_pools = lm_big_programs["n_pools"]                # 6 layers x K, V
    assert "stf_decode_attention_" in text              # the kernel path
    pool_elems = int(np.prod(LM_POOL))
    pool_sized = {}       # opcode -> the entry computation's instructions
    for ln in text[text.index("\nENTRY "):].splitlines():
        m = _HLO_INSTR.match(ln)
        if m and m.group(1) and m.group(2) != "parameter" \
                and pool_elems == int(np.prod(
                    [int(x) for x in m.group(1).split(",")])):
            pool_sized.setdefault(m.group(2), []).append(ln.strip())
    # (i) the donated state, every pool in it, is aliased to the outputs
    header = text.split("\n", 1)[0]
    assert "input_output_alias" in header
    assert header.count("-alias)") == lm_big_programs["n_state"]
    # (ii) no relayout of a pool: no copy with the pool's element count
    assert not pool_sized.get("copy"), \
        [ln[:120] for ln in pool_sized["copy"][:3]]
    # (iii) one pool-shaped instruction per append, the scatter fusion
    assert set(pool_sized) == {"fusion"}, sorted(pool_sized)
    assert len(pool_sized["fusion"]) == n_pools
    assert all("_append/scatter" in ln for ln in pool_sized["fusion"])


def _entry_instructions(text):
    """(shape, opcode, line) of the entry computation's instructions."""
    for ln in text[text.index("\nENTRY "):].splitlines():
        m = _HLO_INSTR.match(ln)
        if m and m.group(1):
            yield ([int(x) for x in m.group(1).split(",")], m.group(2),
                   ln.strip())


@pytest.mark.parametrize("program,rows", [("decode96", 96), ("prefill8", 8)])
def test_lm_big_attention_reads_the_pool_in_place(lm_big_programs, program,
                                                  rows):
    """PR 30: the paged programs build no logical view. Until then a
    decode call gathered ``bf16[3072,64,1024]`` (96 rows x 32 pages) per
    K and V per layer and relaid it as ``bf16[96,2048,16,64]`` for the
    kernel: 58 % of lm-big.backlog's device time."""
    text = lm_big_programs["texts"][program]
    view_elems = rows * LM_PAGES_PER_SEQ * LM_PAGE_LEN * 16 * 64
    view_sized = [ln[:140] for shape, op, ln in _entry_instructions(text)
                  if op != "parameter" and int(np.prod(shape)) == view_elems]
    assert not view_sized, view_sized[:3]
    assert "bf16[3072,64,1024]" not in text
    assert "bf16[96,2048,16,64]" not in text
    # one paged kernel a layer, named so that the benchmark's
    # decode_attn_ms.serve pattern (stf_decode_attention_q1...) finds it
    kq = 1 if program == "decode96" else LM_PAGE_LEN
    calls = re.findall(
        r"%(stf_decode_attention_q\d+_paged)[\w.\-]* = [^\n]*custom-call",
        text)
    # a prefill call keeps only its appends: the last layer's attention
    # feeds nothing and the compiler drops it
    n_calls = 6 if program == "decode96" else 5
    assert calls == [f"stf_decode_attention_q{kq}_paged"] * n_calls, calls


def test_paged_decode_attention_fits_vmem_at_every_lm_big_bucket():
    import importlib
    import json
    import os

    da = importlib.import_module(
        "simple_tensorflow_tpu.ops.pallas.decode_attention")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "lm-big.json")) as f:
        kw = json.load(f)["program"]["model_kwargs"]
    assert (kw["page_len"], kw["pages_per_seq"]) == (LM_PAGE_LEN,
                                                    LM_PAGES_PER_SEQ)
    # the estimate does not depend on the bucket (the grid's first axis):
    # one number covers every decode bucket, one every prefill bucket
    for kq in (1, LM_PAGE_LEN):
        for dtype in (BF16, F32):
            est = da.paged_vmem_bytes(kq, 16, 64, LM_PAGE_LEN,
                                      LM_PAGES_PER_SEQ, dtype)
            assert est < 14 * 2 ** 20, (kq, dtype, est)
    assert da.paged_heads_per_group(1, 16, 64) == 16
    assert da.paged_heads_per_group(LM_PAGE_LEN, 16, 64) == 4
    # a decode step takes a block of 8 pages a grid step, a prefill
    # block one
    assert da.paged_pages_per_block(1, 16, 64, LM_PAGE_LEN,
                                    LM_PAGES_PER_SEQ, BF16) == 8
    assert da.paged_pages_per_block(LM_PAGE_LEN, 16, 64, LM_PAGE_LEN,
                                    LM_PAGES_PER_SEQ, BF16) == 1


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("rows,kq", [(96, 1), (8, 1), (32, LM_PAGE_LEN),
                                     (1, LM_PAGE_LEN)])
def test_paged_decode_attention_kernel(tpu_compile, dtype, rows, kq):
    """The paged kernel alone at lm-big's pool, for the smallest and the
    largest decode and prefill buckets (Mosaic refuses here what
    interpret mode lets through): a decode step in blocks of several
    pages a grid step, the prefill block one page a step."""
    import importlib

    da = importlib.import_module(
        "simple_tensorflow_tpu.ops.pallas.decode_attention")
    pool = ((LM_PAGES + 1, LM_PAGE_LEN, 16 * 64), dtype)
    q = ((rows, 16, 64) if kq == 1 else (rows, kq, 16, 64), dtype)
    g = da.paged_heads_per_group(kq, 16, 64)
    per_block = da.paged_pages_per_block(kq, 16, 64, LM_PAGE_LEN,
                                         LM_PAGES_PER_SEQ, dtype)
    assert (per_block > 1) == (kq == 1)
    took = kreg.metric_paged_tiles.get_cell(str(kq), str(g), str(per_block))
    before = took.value()
    da._paged_call.clear_cache()
    text = tpu_compile(
        lambda q, k, v, t, n: da.paged_decode_attention(
            q, k, v, t, n, causal_offset=kq > 1),
        q, pool, pool, ((rows, LM_PAGES_PER_SEQ), jnp.int32),
        ((rows,), jnp.int32))
    assert f"stf_decode_attention_q{kq}_paged" in text
    assert took.value() == before + 1
    # multi-head: the block-diagonal tiles it has had since PR 30
    assert f"bf16[{rows},{16 // g},{max(g * kq, 8)},{g * 64}]" in text \
        or dtype != BF16


@pytest.mark.parametrize("rows,kq", [(256, 1), (1, 1), (8, 256), (1, 256)])
def test_paged_decode_attention_kernel_grouped(tpu_compile, rows, kq):
    """Grouped queries, 32 query over 2 key-value heads x 128 at the
    state-space cell's K/V pool (3,361 pages x 256 x 256 lanes): a
    key-value head's 16 query heads are rows of its own tile — 16 rows at
    a decode step, four tiles of 1024 at a 256-query prefill block."""
    import importlib

    da = importlib.import_module(
        "simple_tensorflow_tpu.ops.pallas.decode_attention")
    pool = ((3361, 256, 2 * 128), BF16)
    q = ((rows, 32, 128) if kq == 1 else (rows, kq, 32, 128), BF16)
    text = tpu_compile(
        lambda q, k, v, t, n: da.paged_decode_attention(
            q, k, v, t, n, causal_offset=kq > 1),
        q, pool, pool, ((rows, 13), jnp.int32), ((rows,), jnp.int32))
    assert f"stf_decode_attention_q{kq}_paged" in text
    r_blk = da.grouped_heads_per_tile(kq, 16)
    assert f"bf16[{rows},2,{16 // r_blk},{r_blk * kq},128]" in text


# ---------------------------------------------------------------------------
# The sparse-attention routed-FFN decoder at its benchmark sizes
# (chipbench/configs/keye-vl2-30b-a3b.json): the decode and the page-chunk
# prefill programs have to compile for the described chip, fit beside
# 13.8 GB of weights and caches, keep every state leaf aliased, and leave
# the K and V pools (761 x 512 x 512 bfloat16, 399 MB each) uncopied.
# ---------------------------------------------------------------------------

def _paged_serving_programs(one_chip, config_file, stack_of, decode_bucket,
                            prefill_bucket, kernels_on_tpu=False):
    """The decode and the page-chunk prefill program of a block stack at
    its benchmark configuration's sizes, compiled for the described chip
    from the Session's own donating step function (state as avals:
    nothing is allocated). ``stack_of(cfg_kwargs, model_kwargs)`` ->
    (config, stack)."""
    import json
    import os

    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import causal_lm

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", config_file)) as f:
        program = json.load(f)["program"]
    kw = program["model_kwargs"]

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    graph = stf.Graph()
    with _lowering_for_the_described_chip() as mp, graph.as_default(), \
            stf.Session(graph=graph) as sess:
        if kernels_on_tpu:
            mp.setattr(kreg, "backend", lambda: "tpu")
        cfg, stack = stack_of(program["config_kwargs"], kw)
        # the last-token buffer, and a stack's pools of state by slot,
        # are sized from max_live
        prog = causal_lm.build_paged_lm_program(
            stack, page_len=kw["page_len"],
            pages_per_seq=kw["pages_per_seq"], num_pages=kw["num_pages"],
            decode_bucket_sizes=(decode_bucket,),
            prefill_bucket_sizes=(prefill_bucket,),
            compute_dtype=stf.bfloat16, max_live=kw["max_live"])
        caches = [c for group in prog["caches"] for c in group]
        state = {v.var_name: aval(v.shape.as_list(),
                                  v.dtype.base_dtype.np_dtype)
                 for v in stf.global_variables()}
        state.update({c.name: aval(c.stored_shape, c.dtype.np_dtype)
                      for c in caches + [prog["last_tok"]]})

        def compiled(fetches, feeds):
            step = sess.plan(fetches, feeds=feeds)._step
            feed_avals = {
                t.name: aval(t.shape.as_list(), t.dtype.base_dtype.np_dtype)
                for t in step.feed_tensors}
            return step.jitted.lower(
                dict(state), feed_avals, aval((), jax.random.key(0).dtype),
                aval((), np.uint32)).compile()

        d, p = prog["decode"][decode_bucket], prog["prefill"][prefill_bucket]
        programs = {
            f"decode{decode_bucket}": compiled(
                {"next_tok": d["next_tok"], "logp": d["logp"], **d["extra"]},
                [d["tok"], d["pos"], d["tables"], d["dst"], d["off"],
                 d["slots"]]),
            f"prefill{prefill_bucket}": compiled(
                {"done": p["op"]},
                [p["tok"], p["base"], p["tables"], p["dst"]]
                + [p[k] for k in ("slots", "lens") if k in p]),
        }
    state_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in state.values())
    return {"programs": programs, "n_state": len(state), "caches": caches,
            "state_bytes": state_bytes, "n_pools": len(caches),
            "pool": caches[0].stored_shape, "kw": kw, "cfg": cfg}


@pytest.fixture(scope="module")
def sparse_moe_programs(one_chip):
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import sparse_moe_lm

    def stack_of(cfg_kwargs, kw):
        cfg = sparse_moe_lm.SparseMoEConfig(**cfg_kwargs)
        return cfg, sparse_moe_lm._SparseMoEStack(
            cfg, stf.bfloat16, "causal_lm",
            sparse_moe_lm.attn_tile_pages(kw["pages_per_seq"])
            * kw["page_len"])

    got = _paged_serving_programs(one_chip, "keye-vl2-30b-a3b.json",
                                  stack_of, 16, 1)
    return dict(got, kv_pool=got["pool"])


@pytest.mark.parametrize("program", ["decode16", "prefill1"])
def test_sparse_moe_programs_compile_and_fit(sparse_moe_programs, program):
    compiled = sparse_moe_programs["programs"][program]
    text = compiled.as_text()
    # weights + three caches a layer: 8.75 + 5.08 GB, all donated through
    assert 13.5e9 < sparse_moe_programs["state_bytes"] < 14.2e9
    header = text.split("\n", 1)[0]
    assert header.count("-alias)") == sparse_moe_programs["n_state"]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert sparse_moe_programs["state_bytes"] + temp < 16.0e9, temp
    # the experts run as the compiler's grouped matmul, twice a layer
    assert text.count("ragged-dot-metadata") >= 1
    pool = sparse_moe_programs["kv_pool"]
    assert pool == (761, 512, 512)
    pool_elems = int(np.prod(pool))
    copies = []
    for ln in text[text.index("\nENTRY "):].splitlines():
        m = _HLO_INSTR.match(ln)
        if m and m.group(1) and m.group(2) == "copy" and pool_elems == int(
                np.prod([int(x) for x in m.group(1).split(",")])):
            copies.append(ln.strip()[:120])
    assert not copies, copies[:3]
    if program == "decode16":
        # the selection is a sort of (16, 33792) scores a layer, and only
        # the selected rows are read: no (16, 33792, 512) view anywhere
        assert " sort(" in text
        assert "bf16[16,33792,512]" not in text
        assert "bf16[16,2048,512]" in text


# ---------------------------------------------------------------------------
# The latent-attention routed-FFN decoder at its benchmark sizes
# (chipbench/configs/kimi-k2.7-code.json): one pool of latent rows a layer.
# WHY THE ROW IS 640 WIDE: declared 576 wide (512 latent + 64 rope) the
# compiler lays the pool out positions-minor and every call pays a
# pool-sized relayout — asserted below on the kernel alone — where the
# 640-wide pool (what the tiled layout occupies anyway) is read in place.
# The decode and page-chunk prefill programs then have to compile for the
# described chip, fit beside 13 GB of weights and caches, keep every state
# leaf aliased, append in place, and hold no op of the pool's gathered-view
# shape.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width,in_place", [(576, False), (640, True)])
def test_latent_pool_layout_decides_the_row_width(tpu_compile, width,
                                                  in_place):
    """The decode kernel alone over a (1201, 512, width) bfloat16 pool: the
    576-wide pool arrives positions-minor ({1,2,0}) and is copied whole
    before the kernel; the 640-wide one arrives rows-minor and is not."""
    import importlib

    la = importlib.import_module(
        "simple_tensorflow_tpu.ops.pallas.latent_attention")
    pool = (1201, 512, width)
    text = tpu_compile(
        lambda q, p, t, n: la.paged_latent_attention(
            q, p, t, n, value_dim=512, sm_scale=0.1447),
        ((32, 64, width), BF16), (pool, BF16), ((32, 37), jnp.int32),
        ((32,), jnp.int32))
    assert "stf_latent_attention_q1_paged" in text
    param = re.search(r"bf16\[1201,512,%d\]\{([0-9,]+)[:}]" % width
                      + r"[^\n]* parameter\(", text).group(1)
    copies = [ln for shape, op, ln in _entry_instructions(text)
              if op == "copy" and shape == list(pool)]
    assert (param == "2,1,0") is in_place, param
    assert (not copies) is in_place, copies[:1]


@pytest.fixture(scope="module")
def latent_moe_programs(one_chip):
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import latent_moe_lm

    def stack_of(cfg_kwargs, kw):
        cfg = latent_moe_lm.LatentMoEConfig(**cfg_kwargs)
        return cfg, latent_moe_lm._LatentMoEStack(cfg, stf.bfloat16,
                                                  "causal_lm")

    return _paged_serving_programs(one_chip, "kimi-k2.7-code.json", stack_of,
                                   32, 4, kernels_on_tpu=True)


@pytest.mark.parametrize("program,rows,kq", [("decode32", 32, 1),
                                             ("prefill4", 4, 512)])
def test_latent_moe_programs_compile_fit_and_read_in_place(
        latent_moe_programs, program, rows, kq):
    got = latent_moe_programs
    compiled = got["programs"][program]
    text = compiled.as_text()
    kw, cfg, pool = got["kw"], got["cfg"], got["pool"]
    # the stored layout: one (pages + scratch, page_len, 640) pool a layer
    assert pool == (kw["num_pages"] + 1, kw["page_len"], 640)
    assert got["n_pools"] == cfg.num_layers
    # weights 8.35 GB + the latent pools 4.72 GB, all donated through
    assert 12.9e9 < got["state_bytes"] < 13.3e9, got["state_bytes"]
    header = text.split("\n", 1)[0]
    assert header.count("-alias)") == got["n_state"]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert got["state_bytes"] + temp < 16.0e9, temp
    params = re.findall(r"bf16\[%d,%d,%d\]\{([0-9,]+)[:}][^\n]* parameter\("
                        % pool, text)
    assert len(params) >= cfg.num_layers and set(params) == {"2,1,0"}, params
    # appends in place: no pool-sized copy, one pool-shaped scatter a layer
    pool_elems = int(np.prod(pool))
    pool_sized = {}
    for shape, op, ln in _entry_instructions(text):
        if op != "parameter" and int(np.prod(shape)) == pool_elems:
            pool_sized.setdefault(op, []).append(ln)
    assert not pool_sized.get("copy"), pool_sized["copy"][:1]
    # (a bitcast moves nothing)
    assert set(pool_sized) - {"bitcast"} == {"fusion"}, sorted(pool_sized)
    assert len(pool_sized["fusion"]) == cfg.num_layers
    # the scatter itself (decode), or the compiler's own in-place row
    # update over the pool seen as (pages x page_len, 640) (a 512-row
    # chunk: it carries no op_name but names its aliased operand)
    assert all("_append/scatter" in ln
               or '"aliasing_operands":{"lists":[{' in ln
               for ln in pool_sized["fusion"])
    # no op of the pool's gathered-view shape (rows x pages_per_seq pages)
    view_elems = rows * kw["pages_per_seq"] * kw["page_len"] * pool[2]
    view_sized = [ln[:140] for shape, op, ln in _entry_instructions(text)
                  if op != "parameter" and int(np.prod(shape)) == view_elems]
    assert not view_sized, view_sized[:3]
    assert f"bf16[{rows * kw['pages_per_seq']},{kw['page_len']},640]" \
        not in text
    # one latent kernel a layer under its own name (a prefill call keeps
    # only its appends: the last layer's attention feeds nothing)
    calls = re.findall(
        r"%(stf_latent_attention_q\d+_paged)[\w.\-]* = [^\n]*custom-call",
        text)
    n_calls = cfg.num_layers - (program == "prefill4")
    assert calls == [f"stf_latent_attention_q{kq}_paged"] * n_calls, calls
    # the held experts' grouped matmuls, under the compiler's own name
    assert text.count("ragged-dot-metadata") >= 1


# ---------------------------------------------------------------------------
# The hybrid state-space routed-FFN decoder at its benchmark sizes
# (chipbench/configs/nemotron-3-nano-30b-a3b.json): the decode-256 and the
# largest prefill program have to compile for the described chip, fit
# beside 13.1 GB of weights, pages and state, keep every state leaf aliased
# and UPDATE THE STATE POOLS IN PLACE: 257 slots x 2.1 MB a layer, 539 MB a
# pool, six of them.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def state_space_moe_programs(one_chip):
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import state_space_moe_lm

    def stack_of(cfg_kwargs, kw):
        cfg = state_space_moe_lm.StateSpaceMoEConfig(**cfg_kwargs)
        return cfg, state_space_moe_lm._StateSpaceMoEStack(
            cfg, stf.bfloat16, "causal_lm")

    return _paged_serving_programs(
        one_chip, "nemotron-3-nano-30b-a3b.json", stack_of, 256, 8,
        kernels_on_tpu=True)


@pytest.mark.parametrize("program,rows,kq", [("decode256", 256, 1),
                                             ("prefill8", 8, 256)])
def test_state_space_moe_programs_compile_fit_and_update_in_place(
        state_space_moe_programs, program, rows, kq):
    got = state_space_moe_programs
    compiled = got["programs"][program]
    text = compiled.as_text()
    kw, cfg = got["kw"], got["cfg"]
    h_pools = [c for c in got["caches"] if not c.paged
               and c.dtype.name == "float32"]
    conv_pools = [c for c in got["caches"] if not c.paged
                  and c.dtype.name != "float32"]
    kv_pools = [c for c in got["caches"] if c.paged]
    assert (len(h_pools), len(conv_pools), len(kv_pools)) == (6, 6, 4)
    assert h_pools[0].stored_shape == (257, 32, 128, 128)
    assert conv_pools[0].stored_shape == (257, 3 * 6144)
    assert kv_pools[0].stored_shape == (3361, 256, 256)
    # weights 8.07 GB as stored (7.85 GB and the experts' zero columns) +
    # K/V pages 1.76 GB + state 3.29 GB, donated through
    assert 13.0e9 < got["state_bytes"] < 13.3e9, got["state_bytes"]
    header = text.split("\n", 1)[0]
    assert header.count("-alias)") == got["n_state"]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert got["state_bytes"] + temp < 16.0e9, temp
    # in place: nothing in the entry computation of a pool's size but the
    # parameters, the update itself and bitcasts — no copy of a
    # (257, 32, 128, 128) float32 buffer, nor of a K/V or window pool
    for pool in (h_pools[0], conv_pools[0], kv_pools[0]):
        elems = int(np.prod(pool.stored_shape))
        sized = {}
        for shape, op, ln in _entry_instructions(text):
            if op != "parameter" and int(np.prod(shape)) == elems:
                sized.setdefault(op, []).append(ln)
        copies = sized.get("copy", [])
        if pool is conv_pools[0]:
            # the 9.5 MB window pool: the compiler may stage it through
            # its fast memory (layout ...S(1)) around the gather and the
            # scatter; it is never copied within HBM
            copies = [ln for ln in copies if "S(1)}" not in ln]
        assert not copies, (pool, copies[:1])
    if program == "decode256":
        # one state-update kernel a state-space layer, by its own name,
        # and the grouped attention kernel once an attention layer
        calls = re.findall(
            r"%(stf_ssm_state_update_b\d+)[\w.\-]* = [^\n]*custom-call",
            text)
        assert calls == ["stf_ssm_state_update_b256"] * 6, calls
    attn = re.findall(
        r"%(stf_decode_attention_q\d+_paged)[\w.\-]* = [^\n]*custom-call",
        text)
    # (a prefill call keeps only its appends and states: the last layer
    # of the cut is an attention layer, whose output feeds nothing)
    assert attn == [f"stf_decode_attention_q{kq}_paged"] * (
        2 - (program == "prefill8")), attn
    assert "_paged_gqa" in text
    # the held experts: the grouped matmul at 2048 rows, every held expert
    # over every row at 256 (ops/moe_ops.py, "THE DENSE FORM")
    assert (text.count("ragged-dot-metadata") >= 1) == (
        program == "prefill8")


# ---------------------------------------------------------------------------
# glm-5.3-flash (models/hybrid_linear_moe_lm.py): the decode program of the
# cell's 128 rows and the page-chunk prefill of 4 rows compile for the
# described chip beside 13.6 GB of weights, latent pages, pooled keys and
# KDA state, keep every state leaf aliased and UPDATE THE KDA STATE POOLS IN
# PLACE: 129 slots x 4.2 MB a layer, 541 MB a pool, four of them.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hybrid_linear_moe_programs(one_chip):
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import hybrid_linear_moe_lm

    def stack_of(cfg_kwargs, kw):
        cfg = hybrid_linear_moe_lm.HybridLinearMoEConfig(**cfg_kwargs)
        return cfg, hybrid_linear_moe_lm._HybridLinearMoEStack(
            cfg, stf.bfloat16, "causal_lm", kw["page_len"])

    return _paged_serving_programs(
        one_chip, "glm-5.3-flash.json", stack_of, 128, 4,
        kernels_on_tpu=True)


@pytest.mark.parametrize("program", ["decode128", "prefill4"])
def test_hybrid_linear_moe_programs_compile_fit_and_update_in_place(
        hybrid_linear_moe_programs, program):
    got = hybrid_linear_moe_programs
    compiled = got["programs"][program]
    text = compiled.as_text()
    s_pools = [c for c in got["caches"] if not c.paged
               and c.dtype.name == "float32"]
    conv_pools = [c for c in got["caches"] if not c.paged
                  and c.dtype.name != "float32"]
    latent = [c for c in got["caches"] if c.paged
              and c.dtype.name != "float32"]
    pooled = [c for c in got["caches"] if c.paged
              and c.dtype.name == "float32"]
    assert [len(p) for p in (s_pools, conv_pools, latent, pooled)] == [
        4, 4, 1, 1]
    assert s_pools[0].stored_shape == (129, 64, 128, 128)
    assert conv_pools[0].stored_shape == (129, 3 * 3 * 8192)
    assert latent[0].stored_shape == (3233, 512, 512)
    assert pooled[0].stored_shape == (3233, 128, 128)
    print(program, "state", got["state_bytes"],
          "temp", compiled.memory_analysis().temp_size_in_bytes)
    header = text.split("\n", 1)[0]
    assert header.count("-alias)") == got["n_state"]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert got["state_bytes"] + temp < 16.5e9, temp
    for pool in (s_pools[0], latent[0]):
        elems = int(np.prod(pool.stored_shape))
        copies = [ln for shape, op, ln in _entry_instructions(text)
                  if op == "copy" and int(np.prod(shape)) == elems]
        assert not copies, (pool, copies[:1])
    if program == "decode128":
        calls = re.findall(
            r"%(stf_kda_state_update_b\d+)[\w.\-]* = [^\n]*custom-call",
            text)
        assert calls == ["stf_kda_state_update_b128"] * 4, calls
        assert re.findall(
            r"%(stf_latent_attention_q1_paged)[\w.\-]* = [^\n]*custom-call",
            text) == ["stf_latent_attention_q1_paged"]


# ---------------------------------------------------------------------------
# lfm2-8b-a1b (models/short_conv_moe_lm.py): the decode program of the cell's
# 256 rows and the page-chunk prefill of 8 rows compile for the described
# chip beside 14.5 GB of weights, K/V pages and windows, keep every state
# leaf aliased, update the windows in place by the short-conv kernel and read
# K/V through the grouped kernel at head_dim 64.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def short_conv_moe_programs(one_chip):
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import short_conv_moe_lm

    def stack_of(cfg_kwargs, kw):
        cfg = short_conv_moe_lm.ShortConvMoEConfig(**cfg_kwargs)
        return cfg, short_conv_moe_lm._ShortConvMoEStack(
            cfg, stf.bfloat16, "causal_lm")

    return _paged_serving_programs(
        one_chip, "lfm2-8b-a1b.json", stack_of, 256, 8,
        kernels_on_tpu=True)


@pytest.mark.parametrize("program,kq", [("decode256", 1), ("prefill8", 256)])
def test_short_conv_moe_programs_compile_fit_and_update_in_place(
        short_conv_moe_programs, program, kq):
    got = short_conv_moe_programs
    compiled = got["programs"][program]
    text = compiled.as_text()
    windows = [c for c in got["caches"] if not c.paged]
    kv_pools = [c for c in got["caches"] if c.paged]
    assert (len(windows), len(kv_pools)) == (10, 6)
    # a slot's two rows of u as whole (16, 128) bfloat16 tiles
    assert windows[0].stored_shape == (257, 32, 128)
    assert kv_pools[0].stored_shape == (3361, 256, 8 * 64)
    # weights 9.21 GB + K/V pages 5.29 GB + windows 21 MB, donated through
    assert 14.4e9 < got["state_bytes"] < 14.6e9, got["state_bytes"]
    header = text.split("\n", 1)[0]
    assert header.count("-alias)") == got["n_state"]
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(program, "state", got["state_bytes"], "temp", temp)
    assert got["state_bytes"] + temp < 16.0e9, temp
    for pool in (windows[0], kv_pools[0]):
        elems = int(np.prod(pool.stored_shape))
        copies = [ln for shape, op, ln in _entry_instructions(text)
                  if op == "copy" and int(np.prod(shape)) == elems]
        assert not copies, (pool, copies[:1])
    calls = re.findall(
        r"%(stf_short_conv_update_b\d+)[\w.\-]* = [^\n]*custom-call", text)
    if program == "decode256":
        # one window update a conv layer, and no loop over the rows
        assert calls == ["stf_short_conv_update_b256"] * 10, calls
        assert not re.search(r"\bwhile\(", text.split("ENTRY", 1)[1])
    else:
        assert not calls
    attn = re.findall(
        r"%(stf_decode_attention_q\d+_paged_gqa)[\w.\-]* = [^\n]*custom-call",
        text)
    assert attn == [f"stf_decode_attention_q{kq}_paged_gqa"] * 3, attn
