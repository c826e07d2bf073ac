"""The short-convolution routed-FFN decoder (LFM2's block,
models/short_conv_moe_lm.py) on the CPU at tiny widths, in float32, against
the benchmark's plain reference (chipbench/reference/short_conv_moe_decoder
.py: the convolution a padded sum over the whole sequence, plain causal
attention, every expert a loop, no cache, no slots, nothing of the program
imported): the window's carry, the decode kernel, the grouped attention
kernel at head_dim 64, the routing's bias and eps, the tied head, and the
programs of the five stacks the benchmark already had, op for op as they
were."""

import dataclasses
import hashlib
import importlib
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
from chipbench.reference import short_conv_moe_decoder as ref  # noqa: E402
from chipbench.runners import serve_short_conv_moe as runner  # noqa: E402
from chipbench.tests import tiny_short_conv_moe  # noqa: E402
from simple_tensorflow_tpu.kernels import registry as kreg  # noqa: E402
from simple_tensorflow_tpu.models import short_conv_moe_lm as lm  # noqa: E402
from simple_tensorflow_tpu.ops import kv_cache_ops as kvc  # noqa: E402
from simple_tensorflow_tpu.ops import moe_ops  # noqa: E402
from simple_tensorflow_tpu.ops import ssm_ops  # noqa: E402

sc = importlib.import_module(
    "simple_tensorflow_tpu.ops.pallas.short_conv_update")
da = importlib.import_module(
    "simple_tensorflow_tpu.ops.pallas.decode_attention")

PAGE, PAGES_PER_SEQ, SEED = 8, 6, 20420001
# float32 against float32: the clean program reads ~1e-6 on the worst logit
# (attention and the routed sum add in other orders than the reference's
# plain forms); the float8 control reads 0.1 and more
TOL = 2e-5


def _config():
    """The benchmark's configuration file cut to tiny widths, float32 (the
    cut chipbench's own rehearsals use): conv+dense, then attention, two
    conv, attention with routed feed-forwards; 8 experts, top-4; d_model
    128 (the window in whole lane tiles) and head_dim 64 over 2 key-value
    heads (two of them to one lane tile in the grouped kernel)."""
    config = tiny_short_conv_moe.config("float32")
    return config, lm.ShortConvMoEConfig(
        **config["program"]["config_kwargs"])


@pytest.fixture(scope="module", params=["auto", "force"])
def served(request):
    """The model with the reference's seeded weights, its programs under
    ``auto`` (XLA compositions on the CPU) or ``force`` (the Pallas
    kernels in interpret mode)."""
    config, cfg = _config()
    was = kreg.default_mode()
    kreg.set_mode(request.param)
    try:
        model = lm.ShortConvMoEGenerativeModel(
            cfg, page_len=PAGE, pages_per_seq=PAGES_PER_SEQ, num_pages=30,
            max_live=4, init_fresh=True, seed=0, compute_dtype=stf.float32,
            aot_warmup=False, prefill_bucket_sizes=[1, 4],
            metrics_label="tiny_short_conv_moe")
        runner.load_weights(model, config, SEED)
        yield model, config, cfg, request.param
        model.close()
    finally:
        kreg.set_mode(was)


def _table(model, pages):
    table = np.full((PAGES_PER_SEQ,), model.scratch_page, np.int32)
    table[:len(pages)] = pages
    return table


def _chunks(model, prompt, table, slot):
    """``prompt[:-1]``'s page chunks as rows, as the engine makes them:
    ``(tok, bases, tables, dst, slots, lens)``."""
    n = -(-(len(prompt) - 1) // PAGE)
    body = np.full((n * PAGE,), model.pad_id, np.int32)
    body[:len(prompt) - 1] = prompt[:-1]
    return (body.reshape(n, PAGE), [PAGE * c for c in range(n)],
            np.stack([table] * n), table[:n], [slot] * n,
            [min(PAGE, len(prompt) - 1 - PAGE * c) for c in range(n)])


def _prefill(model, prompt, pages, slot):
    """The prompt's chunks as the rows of ONE call; returns its table
    ``(1, pages_per_seq)``."""
    table = _table(model, pages)
    tok, bases, tables, dst, slots, lens = _chunks(model, prompt, table,
                                                   slot)
    model.prefill_chunk(tok, bases, tables, dst, slots=slots, lens=lens)
    return table[None]


def _greedy(model, prompt, tables, slot, steps):
    _, p = model._decode_plans[1]
    tok, pos, got, toks = int(prompt[-1]), len(prompt) - 1, [], []
    for _ in range(steps):
        feed = {p["tok"]: np.asarray([tok], np.int32),
                p["pos"]: np.asarray([pos], np.int32), p["tables"]: tables,
                p["dst"]: tables[:, pos // PAGE],
                p["off"]: np.asarray([pos % PAGE], np.int32),
                p["slots"]: np.asarray([slot], np.int32)}
        logits, nxt = model.session.run([p["logits"], p["next_tok"]], feed)
        got.append(logits[0])
        tok = int(nxt[0])
        toks.append(tok)
        pos += 1
    return toks, np.stack(got)


def _reference_logits(spec, prompt, toks, precision="f32"):
    seq = list(prompt) + list(toks)
    return np.asarray(ref.logits_at(
        spec, SEED, [seq], [len(prompt) - 1 + np.arange(len(toks))],
        precision)[0])


@pytest.fixture(scope="module")
def decoded(served):
    """Prefill 29 tokens (4 page chunks, the last one partial, as the rows
    of one call: the window carried from chunk to chunk and between rows
    of one slot), then 12 decode positions."""
    model, config, cfg, _ = served
    rng = np.random.default_rng(1)
    prompt = rng.integers(2, cfg.vocab_size, size=30).astype(np.int32)
    tables = _prefill(model, prompt, [3, 7, 11, 2, 9, 13], slot=2)
    toks, got = _greedy(model, prompt, tables, 2, 12)
    return prompt, toks, got


class TestProgramAgainstReference:
    def test_prefill_then_decode_logits(self, served, decoded):
        model, config, cfg, mode = served
        prompt, toks, got = decoded
        want = _reference_logits(config["reference"]["spec"], prompt, toks)
        assert np.abs(got - want).max() < TOL, (mode, np.abs(got - want))

    def test_the_float8_control_fails_the_tolerance(self, served, decoded):
        model, config, cfg, mode = served
        if mode != "auto":
            pytest.skip("one reading of the control is enough")
        prompt, toks, _ = decoded
        spec = config["reference"]["spec"]
        good = _reference_logits(spec, prompt, toks)
        low = _reference_logits(spec, prompt, toks, "fp8")
        assert np.abs(low - good).max() > 100 * TOL

    def test_a_reused_slot_and_a_one_token_prompt_start_from_zero(
            self, served):
        """Slot 1 serves a first prompt, then a prompt of one token (no
        prefill row: its first decode step is position 0, the slot fresh
        whatever window it holds), then a prompt prefilled in chunks
        (its first row fresh): each agrees with the reference alone."""
        model, config, cfg, _ = served
        spec = config["reference"]["spec"]
        rng = np.random.default_rng(2)
        first = rng.integers(2, cfg.vocab_size, size=13).astype(np.int32)
        _greedy(model, first, _prefill(model, first, [4, 5], slot=1), 1, 3)
        one = np.asarray([7], np.int32)
        toks, got = _greedy(model, one, _table(model, [4, 5])[None], 1, 6)
        assert np.abs(got - _reference_logits(spec, one, toks)).max() < TOL
        again = rng.integers(2, cfg.vocab_size, size=18).astype(np.int32)
        tables = _prefill(model, again, [4, 5, 6], slot=1)
        toks, got = _greedy(model, again, tables, 1, 5)
        assert np.abs(got - _reference_logits(spec, again, toks)).max() \
            < TOL

    def test_two_prompts_in_one_call_each_keep_their_window(self, served):
        """Two prompts' chunks interleaved by base in ONE prefill call,
        as the engine packs an admission: each slot's rows carry its own
        window."""
        model, config, cfg, _ = served
        spec = config["reference"]["spec"]
        rng = np.random.default_rng(3)
        a = rng.integers(2, cfg.vocab_size, size=20).astype(np.int32)
        b = rng.integers(2, cfg.vocab_size, size=12).astype(np.int32)
        ta, tb = _table(model, [8, 10, 12]), _table(model, [14, 15])
        rows_a = _chunks(model, a, ta, 0)
        rows_b = _chunks(model, b, tb, 3)
        order = sorted([(base, 0, i) for i, base in enumerate(rows_a[1])]
                       + [(base, 1, i) for i, base in enumerate(rows_b[1])])
        parts = [[(rows_a, rows_b)[w][k][i] for _, w, i in order]
                 for k in range(6)]
        model.prefill_chunk(np.stack(parts[0]), parts[1],
                            np.stack(parts[2]), parts[3], slots=parts[4],
                            lens=parts[5])
        for prompt, table, slot in ((a, ta, 0), (b, tb, 3)):
            toks, got = _greedy(model, prompt, table[None], slot, 4)
            assert np.abs(got - _reference_logits(spec, prompt, toks)
                          ).max() < TOL


# -- the ops ------------------------------------------------------------------

def _conv_inputs(rng, rows, width, slots, taps=3):
    pool = jnp.asarray(rng.normal(size=(slots,) + sc.window_shape(
        taps, width)), jnp.float32)
    bcx = jnp.asarray(rng.normal(size=(rows, 3 * width)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, width)), jnp.float32)
    return pool, bcx, w


class TestShortConvUpdate:
    def test_update_kernel_equals_composition(self):
        """``ShortConvUpdate``: Pallas (interpret mode) = the gather-update-
        scatter composition = the convolution written out, slots out of
        order, a fresh row, two rows on one slot (the LATER row's window
        stays; both read what the slot held) and padding rows on the
        scratch slot; 48 rows: three grid steps of 16."""
        rng = np.random.default_rng(0)
        rows, width, taps, n_slots = 48, 256, 3, 60
        pool, bcx, w = _conv_inputs(rng, rows, width, n_slots)
        slots = rng.permutation(n_slots - 1)[:rows]
        slots[7] = slots[30]                   # two rows, one slot
        slots[40:] = n_slots - 1               # padding: the scratch slot
        fresh = np.zeros(rows, bool)
        fresh[[3, 40, 41]] = True
        y_k, pool_k = sc.short_conv_update(pool, bcx, w, slots, fresh)
        y_x, pool_x = sc.short_conv_update_xla(pool, bcx, w, slots, fresh)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_x),
                                   rtol=1e-6, atol=1e-6)
        keep = np.arange(n_slots - 1)
        np.testing.assert_array_equal(np.asarray(pool_k)[keep],
                                      np.asarray(pool_x)[keep])
        # written out, row by row, from what the pool held
        held = np.asarray(pool).reshape(n_slots, taps - 1, width)
        b, c, x = np.split(np.asarray(bcx), 3, axis=1)
        u = b * x
        for r in range(rows):
            window = np.zeros((taps - 1, width)) if fresh[r] else \
                held[slots[r]]
            v = (np.asarray(w)[:-1] * window).sum(0) + np.asarray(w)[-1] \
                * u[r]
            np.testing.assert_allclose(np.asarray(y_k)[r], c[r] * v,
                                       rtol=1e-5, atol=1e-5)
            if r != 7 and slots[r] != n_slots - 1:
                np.testing.assert_array_equal(
                    np.asarray(pool_k)[slots[r]].reshape(taps - 1, width),
                    np.concatenate([window[1:], u[r:r + 1]]))
        untouched = np.setdiff1d(keep, slots)
        np.testing.assert_array_equal(np.asarray(pool_k)[untouched],
                                      np.asarray(pool)[untouched])

    def test_bfloat16_rounds_as_the_composition(self):
        rng = np.random.default_rng(1)
        pool, bcx, w = (t.astype(jnp.bfloat16) for t in _conv_inputs(
            rng, 32, 2048, 40))
        slots = np.arange(32)
        fresh = np.arange(32) % 5 == 0
        y_k, pool_k = sc.short_conv_update(pool, bcx, w, slots, fresh)
        y_x, pool_x = sc.short_conv_update_xla(pool, bcx, w, slots, fresh)
        np.testing.assert_array_equal(np.asarray(pool_k, np.float32),
                                      np.asarray(pool_x, np.float32))
        # v's float32 sum in another order may round one bfloat16 step
        # apart before C multiplies it
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_x, np.float32), rtol=1e-2,
                                   atol=1e-2)

    def test_chunk_convolution_carries_into_the_update(self):
        """``CausalConv1D`` over a chunk (the prefill path) leaves the
        window the decode update reads: a chunk then one token equals the
        same tokens as one longer chunk, in the tiled window layout."""
        rng = np.random.default_rng(2)
        width, taps = 128, 3
        pool, _, w = _conv_inputs(rng, 1, width, 3)
        u = jnp.asarray(rng.normal(size=(1, 6, width)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(1, width)), jnp.float32)
        slots, fresh = np.asarray([1]), np.asarray([True])
        whole, _ = ssm_ops.causal_conv1d(pool, u, w, jnp.zeros(width),
                                         slots, fresh, np.asarray([6]))
        part, pool2 = ssm_ops.causal_conv1d(pool, u[:, :5], w,
                                            jnp.zeros(width), slots, fresh,
                                            np.asarray([5]))
        np.testing.assert_allclose(part, whole[:, :5], rtol=1e-6, atol=1e-6)
        bcx = jnp.concatenate([u[:, 5], c, jnp.ones((1, width))], axis=1)
        y, _ = sc.short_conv_update(pool2, bcx, w, slots, np.asarray([False]))
        np.testing.assert_allclose(y, c * whole[:, 5], rtol=1e-5, atol=1e-5)

    def test_routing_of_the_decode_update(self, monkeypatch):
        """At the cell's sizes on a TPU the kernel is taken by its
        roofline; a width of no whole lane tiles is not eligible."""
        from simple_tensorflow_tpu.utils import perf

        monkeypatch.setattr(kreg, "backend", lambda: "tpu")
        monkeypatch.setattr(perf, "chip_spec", lambda device=None:
                            perf.CHIP_TABLE["TPU v5 lite"][:2])
        kd = kreg._KERNELS["ShortConvUpdate"]
        key = kreg.aval_key(jax.ShapeDtypeStruct((256, 6144), jnp.bfloat16),
                            jax.ShapeDtypeStruct((257, 32, 128),
                                                 jnp.bfloat16),
                            jax.ShapeDtypeStruct((3, 2048), jnp.bfloat16))
        assert kd.eligible(key) is None
        assert kreg.decide("ShortConvUpdate", key, mode="auto",
                           count=False) == ("pallas", "cost_model")
        flat = kreg.aval_key(jax.ShapeDtypeStruct((4, 288), jnp.bfloat16),
                             jax.ShapeDtypeStruct((5, 192), jnp.bfloat16),
                             jax.ShapeDtypeStruct((3, 96), jnp.bfloat16))
        assert kd.eligible(flat) == "ineligible_shape"


class TestGroupedAttentionAtHeadDim64:
    """The paged kernel's grouped path at head_dim 64: two key-value heads
    share a lane tile, their query rows stacked in one tile."""

    @pytest.mark.parametrize("kq", [1, 8])
    def test_kernel_equals_plain_attention(self, kq):
        rng = np.random.default_rng(4)
        b, h, h_kv, d, page, n_blocks, pages = 3, 16, 4, 64, 8, 4, 20
        k_pool, v_pool = (jnp.asarray(rng.normal(
            size=(pages, page, h_kv * d)), jnp.float32) for _ in "kv")
        tables = jnp.asarray(rng.permutation(pages)[:b * n_blocks].reshape(
            b, n_blocks), jnp.int32)
        shape = (b, h, d) if kq == 1 else (b, kq, h, d)
        q = jnp.asarray(rng.normal(size=shape), jnp.float32)
        lengths = jnp.asarray([5, 17, 32] if kq == 1 else [0, 8, 16],
                              jnp.int32)
        got = da.paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                        causal_offset=kq > 1)
        # the plain form: each query head against its key-value head's
        # rows of the sequence, through the table
        qf = np.asarray(q).reshape(b, kq, h, d)
        keys = np.asarray(k_pool)[np.asarray(tables)].reshape(
            b, n_blocks * page, h_kv, d)
        vals = np.asarray(v_pool)[np.asarray(tables)].reshape(
            b, n_blocks * page, h_kv, d)
        want = np.zeros_like(qf)
        for i in range(b):
            for j in range(kq):
                seen = int(lengths[i]) + (j + 1 if kq > 1 else 0)
                for hh in range(h):
                    g = hh // (h // h_kv)
                    s = keys[i, :seen, g] @ qf[i, j, hh] / 8.0
                    p = np.exp(s - s.max())
                    want[i, j, hh] = p @ vals[i, :seen, g] / p.sum()
        np.testing.assert_allclose(np.asarray(got).reshape(want.shape),
                                   want, rtol=2e-5, atol=2e-5)

    def test_only_a_head_dim_below_a_tile_packs(self):
        """head_dim 128 (the state-space cell) and the block-diagonal path
        keep their tiles: one key-value head a tile, the same query heads a
        tile as before."""
        assert da.kv_heads_per_lane_tile(64, 8) == 2
        assert da.kv_heads_per_lane_tile(128, 2) == 1
        assert da.kv_heads_per_lane_tile(256, 2) == 1
        # heads that fill no whole tile together keep the old path
        assert da.kv_heads_per_lane_tile(16, 2) == 1
        assert [da.grouped_heads_per_tile(kq, 16) for kq in (1, 256)] \
            == [16, 4]
        assert [da.grouped_heads_per_tile(kq, 4, 2) for kq in (1, 256)] \
            == [4, 2]
        assert da.paged_heads_per_group(1, 16, 64) == 16

    def test_eligible_on_a_tpu(self):
        key = kreg.aval_key(jax.ShapeDtypeStruct((256, 32, 64), jnp.bfloat16),
                            jax.ShapeDtypeStruct((3361, 256, 512),
                                                 jnp.bfloat16),
                            jax.ShapeDtypeStruct((256, 13), jnp.int32))
        assert kreg._KERNELS["PagedDecodeAttention"].eligible(key) is None
        # one key-value head of 64 lanes fills no tile
        one = kreg.aval_key(jax.ShapeDtypeStruct((4, 8, 64), jnp.bfloat16),
                            jax.ShapeDtypeStruct((9, 8, 64), jnp.bfloat16),
                            jax.ShapeDtypeStruct((4, 2), jnp.int32))
        assert kreg._KERNELS["PagedDecodeAttention"].eligible(one) \
            == "ineligible_shape"


class TestRouting:
    def _inputs(self):
        rng = np.random.default_rng(5)
        return (jnp.asarray(rng.normal(size=(64, 16)), jnp.float32),
                jnp.asarray(rng.normal(size=(16, 8)) / 4, jnp.float32))

    def _route(self, bias=None, eps=moe_ops.GATE_NORM_EPS):
        return moe_ops.route(*self._inputs(), top_k=4, norm_topk=True,
                             score="sigmoid", bias=bias, gate_norm_eps=eps)

    def test_the_bias_changes_a_choice_not_a_gate(self):
        experts, gates = self._route()
        bias = jnp.zeros(8).at[5].set(0.3)
        b_experts, b_gates = self._route(bias)
        changed = np.any(np.asarray(experts) != np.asarray(b_experts), 1)
        assert changed.any() and not changed.all()
        # the gates are the chosen experts' own scores over their sum,
        # the bias nowhere in them
        x, wr = self._inputs()
        scores = np.asarray(jax.nn.sigmoid(jnp.dot(
            x, wr, precision=jax.lax.Precision.HIGHEST)))
        chosen = np.take_along_axis(scores, np.asarray(b_experts), 1)
        np.testing.assert_allclose(
            np.asarray(b_gates), chosen / chosen.sum(1, keepdims=True),
            rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(gates)[~changed],
                                      np.asarray(b_gates)[~changed])

    def test_gate_norm_eps_is_honoured(self):
        experts, plain = self._route()
        eps_experts, eps = self._route(eps=0.5)
        np.testing.assert_array_equal(np.asarray(experts),
                                      np.asarray(eps_experts))
        # s / (S + 0.5) against s / S: the ratio is S / (S + 0.5) a row
        ratio = np.asarray(eps) / np.asarray(plain)
        assert np.all(ratio < 1.0)
        np.testing.assert_allclose(
            ratio, np.broadcast_to(ratio[:, :1], ratio.shape), rtol=1e-5)

    def test_the_default_leaves_the_op_as_it_was(self):
        g = stf.Graph()
        with g.as_default():
            args = (stf.zeros([4, 8]), stf.zeros([8, 16]),
                    stf.zeros([16, 8, 8]), stf.zeros([16, 4, 8]))
            plain, _ = moe_ops.routed_ffn_op(*args, top_k=2,
                                             score="sigmoid")
            given, _ = moe_ops.routed_ffn_op(*args, top_k=2,
                                             score="sigmoid",
                                             gate_norm_eps=1e-6)
        assert "gate_norm_eps" not in plain.op.attrs
        assert given.op.attrs["gate_norm_eps"] == 1e-6


class TestServedModel:
    def test_two_kinds_of_pool_and_the_contract(self, served):
        model, config, cfg, mode = served
        if mode != "auto":
            pytest.skip("the graph is the same in both modes")
        assert model.state_outside_pages
        caches = [c for group in model._prog["caches"] for c in group]
        windows = [c for c in caches if not c.paged]
        kv_pools = [c for c in caches if c.paged]
        assert len(windows) == cfg.layer_types.count("conv") == 3
        assert len(kv_pools) == 2 * 2
        assert windows[0].shape == (5, 2, 128)
        assert kv_pools[0].stored_shape == (31, PAGE, 2 * 64)
        types = [op.type for op in model.graph.get_operations()]
        assert {"CausalConv1D", "ShortConvUpdate", "PagedDecodeAttention",
                "RoutedFFN", "RotaryEmbedding"} <= set(types)
        assert "ShortConvUpdate" in kvc.STATE_UPDATE_OP_TYPES
        # routed layers 1-4 each with the eps of the family
        routed = [op for op in model.graph.get_operations()
                  if op.type == "RoutedFFN"]
        assert {op.attrs.get("gate_norm_eps") for op in routed} == {1e-6}

    def test_the_head_is_the_embedding(self, served):
        model, config, cfg, mode = served
        if mode != "auto":
            pytest.skip("the graph is the same in both modes")
        with model.graph.as_default():
            names = {v.name.split(":")[0]
                     for v in stf.trainable_variables()}
        assert "causal_lm/embed" in names
        assert not any("lm_head" in n for n in names)
        assert set(config["variables"].values()) >= {
            "causal_lm/embed", "causal_lm/final_norm"}

    @pytest.mark.parametrize("unsupported", ["int8", "mesh", "tp"])
    def test_paths_it_does_not_have_are_refused(self, unsupported):
        with pytest.raises(ValueError, match=unsupported):
            lm.ShortConvMoEGenerativeModel(
                lm.ShortConvMoEConfig.tiny(), page_len=8,
                init_fresh=True, **{unsupported: 2})

    def test_published_sizes(self):
        cfg = lm.ShortConvMoEConfig()
        assert cfg.num_layers == 24
        assert (cfg.layer_types.count("conv"),
                cfg.layer_types.count("full_attention")) == (18, 6)
        assert [i for i, t in enumerate(cfg.layer_types)
                if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
        full = tiny_short_conv_moe.tiny.load("configs", "lfm2-8b-a1b.json")
        assert list(cfg.layer_types) == full["published"]["layer_types"]
        kw = full["program"]["config_kwargs"]
        assert kw["layer_types"] == list(cfg.layer_types[1:14])
        real = lm.ShortConvMoEConfig(**kw)
        assert (real.d_model, real.num_heads, real.num_kv_heads,
                real.head_dim, real.conv_kernel, real.dense_width,
                real.num_experts, real.experts_per_token,
                real.expert_width, real.vocab_size) == (
            2048, 32, 8, 64, 3, 7168, 32, 4, 1792, 65536)
        # 10 conv layers x 2 x 2048 x 2 B; 3 attention layers x 2 x 8 x 64
        # x 2 B
        assert real.state_bytes_per_slot(2) == 10 * 8192 == 81_920
        assert real.kv_bytes_per_token(2) == 3 * 2048 == 6144
        assert real.state_shape == (32, 128)


class TestTheOthersAreUnchanged:
    """The tied head, the eps, the window's layout and the grouped kernel
    leave the programs of the configurations the benchmark already has op
    for op as they were: the digests are of those graphs, every decode
    program with its last-token buffer (op types, input counts, attribute
    names and values, in order)."""

    KW = dict(page_len=8, pages_per_seq=6, num_pages=30, max_live=4,
              init_fresh=True, seed=0, compute_dtype=stf.float32,
              aot_warmup=False, prefill_bucket_sizes=[1, 2])

    def _build(self, which):
        from simple_tensorflow_tpu.models import causal_lm as cl
        from simple_tensorflow_tpu.models import hybrid_linear_moe_lm as hl
        from simple_tensorflow_tpu.models import latent_moe_lm as la_lm
        from simple_tensorflow_tpu.models import sparse_moe_lm as sm
        from simple_tensorflow_tpu.models import state_space_moe_lm as ss
        from simple_tensorflow_tpu.models.transformer import (
            TransformerConfig)

        if which == "lm-big":
            return cl.CausalLMGenerativeModel(dataclasses.replace(
                TransformerConfig.tiny(), max_len=64), **self.KW)
        if which == "sparse":
            return sm.SparseMoEGenerativeModel(sm.SparseMoEConfig.tiny(),
                                               **self.KW)
        if which == "latent":
            return la_lm.LatentMoEGenerativeModel(
                la_lm.LatentMoEConfig.tiny(), **self.KW)
        if which == "hybrid":
            return hl.HybridLinearMoEGenerativeModel(
                hl.HybridLinearMoEConfig.tiny(), **self.KW)
        return ss.StateSpaceMoEGenerativeModel(
            ss.StateSpaceMoEConfig.tiny(), **self.KW)

    @pytest.mark.parametrize("which,n_ops,digest", [
        ("lm-big", 480, "8c8e7b455a66303f"),
        ("sparse", 520, "f9cd679a69b37436"),
        ("latent", 720, "a539255010769d9a"),
        ("state_space", 672, "dcc6bd746c449066"),
        ("hybrid", 1572, "52022a3b12f47345")])
    def test_op_list_as_before(self, which, n_ops, digest):
        model = self._build(which)
        try:
            ops = model.graph.get_operations()
            sig = [(op.type, len(op.inputs), tuple(sorted(
                (k, repr(v)) for k, v in op.attrs.items()
                if not k.startswith("_src")))) for op in ops]
        finally:
            model.close()
        assert len(ops) == n_ops
        assert hashlib.sha256(repr(sig).encode()).hexdigest()[:16] == digest
        assert not {t for t, _, _ in sig} & {"ShortConvUpdate"}
        assert not any(dict(a).get("gate_norm_eps") for _, _, a in sig)
