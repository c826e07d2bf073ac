"""The hybrid linear-attention routed-FFN decoder (GLM-5.3-Flash's block,
models/hybrid_linear_moe_lm.py) on the CPU at tiny widths, in float32,
against the benchmark's plain reference
(chipbench/reference/hybrid_linear_moe_decoder.py: the delta rule token by
token, every block scored, no chunks, no cache, no slots, nothing of the
program imported): the ops (the chunked scan, the decode kernel, Sinkhorn,
the pooled selection), the chip's share, and the programs of the four
stacks the benchmark already had, op for op as they were."""

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
from chipbench.reference import hybrid_linear_moe_decoder as ref  # noqa: E402
from chipbench.runners import serve_hybrid_linear_moe as runner  # noqa: E402
from chipbench.tests import tiny_hybrid_linear_moe  # noqa: E402
from simple_tensorflow_tpu.kernels import registry as kreg  # noqa: E402
from simple_tensorflow_tpu.models import hybrid_linear_moe_lm as lm  # noqa: E402
from simple_tensorflow_tpu.ops import hyper_connection_ops as hc  # noqa: E402
from simple_tensorflow_tpu.ops import kv_cache_ops as kvc  # noqa: E402
from simple_tensorflow_tpu.ops import linear_attention_ops as la  # noqa: E402
from simple_tensorflow_tpu.ops import moe_ops  # noqa: E402
from simple_tensorflow_tpu.ops import sparse_attention_ops as sa  # noqa: E402

ku = importlib.import_module(
    "simple_tensorflow_tpu.ops.pallas.kda_state_update")

PAGE, PAGES_PER_SEQ, SEED = 8, 6, 20390001
# float32 against float32: the clean program reads 3e-6 on the worst logit
# in auto mode and 5e-6 with the kernels interpreted (the chunked scan and
# the absorbed attention sum in other orders than the reference's token
# scan and plain form); the float8 control reads 0.5 and more
TOL = 5e-5


def _config():
    """The benchmark's configuration file cut to tiny widths, float32 (the
    cut chipbench's own rehearsals use): KDA+dense, DSA+routed, two
    KDA+routed; 16 experts of which this chip holds 8 (experts 4..11),
    top-4; index_topk 12 = the tail block and 2 blocks of 4, so every
    context past 12 positions is chosen from; chunk 4."""
    config = tiny_hybrid_linear_moe.config("float32")
    return config, lm.HybridLinearMoEConfig(
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
        model = lm.HybridLinearMoEGenerativeModel(
            cfg, page_len=PAGE, pages_per_seq=PAGES_PER_SEQ, num_pages=30,
            max_live=4, init_fresh=True, seed=0, compute_dtype=stf.float32,
            aot_warmup=False, prefill_bucket_sizes=[1, 4],
            metrics_label="tiny_hybrid_linear_moe")
        runner.load_weights(model, config, SEED)
        yield model, config, cfg, request.param
        model.close()
    finally:
        kreg.set_mode(was)


def _prefill(model, prompt, pages, slot):
    """``prompt[:-1]``'s page chunks as the rows of one call, as the
    engine makes them; returns the prompt's table ``(1, pages_per_seq)``."""
    table = np.full((PAGES_PER_SEQ,), model.scratch_page, np.int32)
    table[:len(pages)] = pages
    n = -(-(len(prompt) - 1) // PAGE)
    body = np.full((n * PAGE,), model.pad_id, np.int32)
    body[:len(prompt) - 1] = prompt[:-1]
    model.prefill_chunk(
        body.reshape(n, PAGE), [PAGE * c for c in range(n)],
        np.stack([table] * n), table[:n], slots=[slot] * n,
        lens=[min(PAGE, len(prompt) - 1 - PAGE * c) for c in range(n)])
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
    """Prefill 30 tokens through both kinds of pool (4 page chunks, the
    last one partial, and a pooled block cut by the prompt's end that the
    first decode steps complete), then 14 decode positions: contexts
    31-44, past index_topk 12 all along."""
    model, config, cfg, _ = served
    rng = np.random.default_rng(1)
    prompt = rng.integers(2, cfg.vocab_size, size=31).astype(np.int32)
    tables = _prefill(model, prompt, [3, 7, 11, 2, 9, 13], slot=2)
    toks, got = _greedy(model, prompt, tables, 2, 14)
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

    def test_a_prompt_of_one_token_starts_from_zero_at_decode(self, served):
        """No prefill row at all: the first decode step is position 0, its
        slot fresh — whatever state the slot holds from the sequence
        before."""
        model, config, cfg, _ = served
        prompt = np.asarray([7], np.int32)
        table = np.full((1, PAGES_PER_SEQ), model.scratch_page, np.int32)
        table[0, :2] = [5, 6]
        toks, got = _greedy(model, prompt, table, 2, 6)
        want = _reference_logits(config["reference"]["spec"], prompt, toks)
        assert np.abs(got - want).max() < TOL


# -- the ops -------------------------------------------------------------------

def _recurrence(s, q, k, v, g, beta, scale):
    """Token by token, numpy float64: ``q, k, g (L, H, K)``, ``v (L, H,
    V)``, ``beta (L, H)`` from state ``s (H, K, V)``."""
    s = np.asarray(s, np.float64)
    out = []
    for t in range(q.shape[0]):
        s = np.exp(g[t])[..., None] * s
        ks = np.einsum("hk,hkv->hv", k[t], s)
        s = s + k[t][..., None] * (beta[t][:, None] * (v[t] - ks))[:, None]
        out.append(np.einsum("hk,hkv->hv", q[t], s) * scale)
    return np.stack(out), s


def _kda_inputs(rng, lead, heads, dk, dv):
    def unit(x):
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(
            np.float32)
    return (unit(rng.normal(size=lead + (heads, dk))),
            unit(rng.normal(size=lead + (heads, dk))),
            rng.normal(size=lead + (heads, dv)).astype(np.float32),
            (-5.0 * rng.uniform(size=lead + (heads, dk))).astype(np.float32),
            rng.uniform(0.05, 1.0, size=lead + (heads,)).astype(np.float32))


class TestKDA:
    H, K, V = 2, 16, 8

    @pytest.mark.parametrize("pinned", [False, True])
    def test_chunk_scan_at_chunk_64_equals_the_token_recurrence(
            self, pinned):
        """Rows of 128 tokens in blocks of 64, in one call: a row from its
        slot, a fresh row, a row that continues the first one's slot, a
        padded tail. ``pinned``: every gate at the -5 bound over the whole
        first block of every row, where the cumulative log-decay reaches
        -320 and ``exp(-G)`` alone would overflow float32."""
        rng = np.random.default_rng(7)
        q, k, v, g, beta = _kda_inputs(rng, (3, 128), self.H, self.K,
                                       self.V)
        if pinned:
            g[:, :64] = -5.0
        pool = rng.normal(size=(5, self.H, self.K, self.V)).astype(
            np.float32)
        slots = np.asarray([3, 0, 3], np.int32)
        fresh = np.asarray([False, True, False])
        lens = np.asarray([128, 100, 77], np.int32)
        o, got = la.kda_chunk_scan(jnp.asarray(pool), q, k, v, g, beta,
                                   slots, fresh, lens, chunk=64, scale=0.25)
        o, got = np.asarray(o), np.asarray(got)
        assert np.isfinite(o).all() and np.isfinite(got).all()
        o0, s0 = _recurrence(pool[3], q[0], k[0], v[0], g[0], beta[0], 0.25)
        o1, s1 = _recurrence(np.zeros_like(pool[0]), q[1, :100], k[1, :100],
                             v[1, :100], g[1, :100], beta[1, :100], 0.25)
        o2, s2 = _recurrence(s0, q[2, :77], k[2, :77], v[2, :77],
                             g[2, :77], beta[2, :77], 0.25)
        # float32 against float64: the pair decays are made from
        # cumulative sums of up to 64 log-decays (absolute error ~2e-5 at
        # -320), the products at the highest matmul precision
        for a, b in ((o[0], o0), (o[1, :100], o1), (o[2, :77], o2),
                     (got[0], s1), (got[3], s2)):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
        # of two rows of one slot the last one's state stays there, the
        # first one's goes to the scratch slot (the pool's last row);
        # the slots this call names no row of keep what they held
        np.testing.assert_allclose(got[4], s0, atol=2e-5, rtol=1e-4)
        for s in (1, 2):
            np.testing.assert_array_equal(got[s], pool[s])

    def test_decode_kernel_equals_composition_equals_the_recurrence(self):
        """``KDAStateUpdate``: Pallas (interpret mode) = the gather-update-
        scatter composition = one token of the recurrence; rows whose
        slots are out of order, a fresh row from zero."""
        rng = np.random.default_rng(3)
        heads = 16                           # two blocks of 8 heads
        q, k, v, g, beta = _kda_inputs(rng, (5,), heads, self.K, self.K)
        pool = rng.normal(size=(7, heads, self.K, self.K)).astype(
            np.float32)
        slots = np.asarray([4, 0, 6, 2, 5], np.int32)
        fresh = np.asarray([False, True, False, False, True])
        args = (q, k, v, g, beta, slots, fresh)
        o_k, pool_k = ku.kda_state_update(jnp.asarray(pool), *args,
                                          scale=0.25)
        o_x, pool_x = ku.kda_state_update_xla(jnp.asarray(pool), *args,
                                              scale=0.25)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_x),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(pool_k), np.asarray(pool_x),
                                   atol=1e-6)
        for i in range(5):
            s0 = np.zeros_like(pool[0]) if fresh[i] else pool[slots[i]]
            o1, s1 = _recurrence(s0, q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                 g[i:i + 1], beta[i:i + 1], 0.25)
            np.testing.assert_allclose(np.asarray(o_k[i]), o1[0], atol=1e-5)
            np.testing.assert_allclose(np.asarray(pool_k[slots[i]]), s1,
                                       atol=1e-5)
        for s in (1, 3):
            np.testing.assert_array_equal(np.asarray(pool_k[s]), pool[s])
        assert ku.heads_per_block(64, 128, 128) == 16

    def test_routing_of_the_decode_update(self):
        assert "KDAStateUpdate" in kreg.kernel_types()
        q = jax.ShapeDtypeStruct((128, 64, 128), jnp.float32)
        key = kreg.aval_key(
            q, jax.ShapeDtypeStruct((129, 64, 128, 128), jnp.float32), q)
        kd = kreg._KERNELS["KDAStateUpdate"]
        assert kreg._route(kd, key, "auto", "cpu") == (
            "xla", "interpret_backend")
        assert kreg._route(kd, key, "auto", "tpu") == ("pallas",
                                                       "cost_model")
        assert kreg._route(kd, key, "off", "tpu") == ("xla", "mode_off")
        wrong = kreg.aval_key(
            q, jax.ShapeDtypeStruct((129, 32, 128, 128), jnp.float32), q)
        assert kreg._route(kd, wrong, "force", "tpu") == (
            "xla", "ineligible_shape")

    def test_selected_rows_take_the_paged_latent_kernel(self):
        """The selected latent rows are read by the paged latent kernel as
        a pool of their own: pages of 512 rows, the lengths ``n_valid``;
        the kernel (interpreted) equals the composition."""
        rng = np.random.default_rng(2)
        b, h, k, w = 3, 4, 32, 16
        q = jnp.asarray(rng.normal(size=(b, h, w)), jnp.float32)
        rows = jnp.asarray(rng.normal(size=(b, k, w)), jnp.float32)
        n_valid = jnp.asarray([32, 5, 17], jnp.int32)
        assert sa.largest_divisor(2048) == 512 and sa.largest_divisor(k) == k
        got = {}
        for mode in ("force", "off"):
            with kreg.activate(mode):
                got[mode] = np.asarray(sa.selected_latent_attention(
                    q, rows, n_valid, sm_scale=0.3))
        np.testing.assert_allclose(got["force"], got["off"], atol=1e-5)
        for i in range(b):
            n = int(n_valid[i])
            s = np.einsum("hw,kw->hk", q[i], rows[i, :n]) * 0.3
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            np.testing.assert_allclose(got["off"][i], p @ np.asarray(
                rows[i, :n]), atol=1e-5)


class TestHyperConnection:
    def test_sinkhorn_rows_and_columns_sum_to_one(self):
        """20 iterations, each a row then a column normalisation: the
        columns sum to 1 after the last whatever the logits; the rows
        converge within 1e-5 for logits of std 0.5 (4,096 matrices) but
        not for widely spread ones — 20 iterations is the published
        setting, and the reference iterates as many times."""
        rng = np.random.default_rng(0)
        for std, row_tol in ((0.5, 1e-5), (2.0, 0.05)):
            logits = jnp.asarray(rng.normal(scale=std, size=(4096, 4, 4)),
                                 jnp.float32)
            m = np.asarray(hc.sinkhorn(jnp.exp(logits), iters=20, eps=1e-6))
            assert (m > 0).all()
            np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
            np.testing.assert_allclose(m.sum(-1), 1.0, atol=row_tol)
            # the reference's own loop gives the same matrix
            np.testing.assert_allclose(
                m, np.asarray(ref.sinkhorn(jnp.exp(logits), 20, 1e-6)),
                atol=1e-6)

    def test_streams_through_a_sublayer_against_the_reference(self):
        rng = np.random.default_rng(1)
        n, d, r = 4, 8, 5
        x = jnp.asarray(rng.normal(size=(r, n, d)), jnp.float32)
        lp = {"hc_phi": jnp.asarray(rng.normal(size=(n * d, 24)) / 6,
                                    jnp.float32),
              "hc_scale": jnp.asarray([1.1, 0.9, 1.3], jnp.float32),
              "hc_bias": jnp.asarray(rng.normal(size=(24,)), jnp.float32)}
        spec = {"rms_eps": 1e-5, "hc_iters": 20, "hc_eps": 1e-6}

        def f(h):
            return jnp.tanh(h) * 2.0

        want = ref.hyper(x, lp, "hc", spec, f)
        h, post, res = hc.hc_pre(x, lp["hc_phi"], lp["hc_scale"],
                                 lp["hc_bias"], iters=20, eps=1e-6,
                                 norm_eps=1e-5)
        got = hc.hc_post(x, f(h), post, res)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        assert ((np.asarray(post) > 0) & (np.asarray(post) < 2)).all()


class TestPooledSelection:
    def _brute(self, scores, t, topk, pool):
        """The positions query t reads, by a sorted list."""
        tail = t // pool
        kb = topk // pool - 1
        order = sorted(range(tail), key=lambda b: (-scores[b], b))[:kb]
        read = set(range(tail * pool, t + 1))
        for b in order:
            read |= set(range(b * pool, b * pool + pool))
        return read

    @pytest.mark.parametrize("t", [0, 3, 13, 18, 29, 30, 31, 47])
    def test_decode_choice_against_a_brute_force_with_ties(self, t):
        rng = np.random.default_rng(t)
        pool, topk, lp, hi, di = 4, 16, 12, 2, 8
        q = rng.normal(size=(1, hi, di)).astype(np.float32)
        w = np.abs(rng.normal(size=(1, hi))).astype(np.float32)
        keys = rng.normal(size=(1, lp, di)).astype(np.float32)
        keys[0, 5] = keys[0, 1]              # a tie: the lower block first
        keys[0, 7] = keys[0, 1]
        scores = np.asarray(sa._pooled_scores(q, w, keys))[0]
        pos, n = sa.indexer_topk_pooled(q, w, keys, np.asarray([t + 1]),
                                        topk=topk, pool=pool)
        n = int(n[0])
        got = set(np.asarray(pos)[0, :n].tolist())
        assert got == self._brute(scores, t, topk, pool)
        assert len(got) == n and np.asarray(pos).shape == (1, topk)

    def test_prefill_choice_equals_the_decode_choice_row_by_row(self):
        rng = np.random.default_rng(5)
        pool, topk, lp, hi, di, s_len = 4, 16, 12, 2, 8, 16
        base = np.asarray([32, 8], np.int32)
        q = rng.normal(size=(2, s_len, hi, di)).astype(np.float32)
        w = np.abs(rng.normal(size=(2, s_len, hi))).astype(np.float32)
        keys = rng.normal(size=(2, lp, di)).astype(np.float32)
        keys[:, 3] = keys[:, 0]
        chosen = np.asarray(sa.pooled_block_choice(q, w, keys, base,
                                                   topk=topk, pool=pool))
        for b in range(2):
            for j in range(s_len):
                t = int(base[b]) + j
                pos, n = sa.indexer_topk_pooled(
                    q[b:b + 1, j], w[b:b + 1, j], keys[b:b + 1],
                    np.asarray([t + 1]), topk=topk, pool=pool)
                blocks = {p // pool for p in
                          np.asarray(pos)[0, :int(n[0])].tolist()}
                blocks.discard(t // pool)
                assert set(np.flatnonzero(chosen[b, j])) == blocks, (b, j)

    def test_interleaved_rotation(self):
        x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 2, 8)),
                        jnp.float32)
        pos = jnp.asarray([0, 5, 9])
        got = np.asarray(sa.rotary_embedding(x, pos, theta=100.0,
                                             interleave=True))
        spec = {"idx_rope_dim": 8, "idx_theta": 100.0}
        np.testing.assert_allclose(
            got, np.asarray(ref._rope_interleaved(x, pos, spec)), atol=1e-6)
        np.testing.assert_allclose(got[0], np.asarray(x[0]), atol=1e-7)


class TestChipsShare:
    """Four shares of the 16 experts, the shared expert counted once, add
    up to what the uncut reference gives for the whole layer — with the
    SwiGLU clamp at work (a limit the products cross)."""
    E, K, H, W = 16, 4, 16, 8
    LIMIT = 0.5

    def _layer(self, t=24, seed=3):
        ks = jax.random.split(jax.random.key(seed), 7)
        h, e, w = self.H, self.E, self.W
        return {"x": jax.random.normal(ks[0], (t, h)),
                "wr": jax.random.normal(ks[1], (h, e)),
                "bias": 0.3 * jax.random.normal(ks[2], (e,)),
                "w_gate_up": 0.5 * jax.random.normal(ks[3], (e, h, 2 * w)),
                "w_down": 0.3 * jax.random.normal(ks[4], (e, w, h)),
                "ws_gate_up": 0.5 * jax.random.normal(ks[5], (h, 2 * w)),
                "ws_down": 0.3 * jax.random.normal(ks[6], (w, h))}

    def _spec(self, held):
        return {"experts": self.E, "experts_per_token": self.K,
                "held": list(held), "gate_scale": 2.5, "norm_topk": True,
                "swiglu_limit": self.LIMIT}

    def test_four_shares_add_up_to_the_uncut_layer(self):
        lp = self._layer()
        with jax.default_matmul_precision("highest"):
            parts = [moe_ops.routed_ffn(
                lp["x"], lp["wr"], lp["w_gate_up"][4 * c:4 * c + 4],
                lp["w_down"][4 * c:4 * c + 4], top_k=self.K,
                score="sigmoid", bias=lp["bias"], gate_scale=2.5,
                held=(4 * c, 4), swiglu_limit=self.LIMIT)
                for c in range(4)]
            total = sum(np.asarray(y) for y, _ in parts) + np.asarray(
                ref.shared_part(lp["x"], lp, self._spec((0, self.E))))
            want = (ref.routed_part(lp["x"], lp, self._spec((0, self.E)))
                    + ref.shared_part(lp["x"], lp, self._spec((0, self.E))))
            unclamped = ref.routed_part(lp["x"], lp, dict(
                self._spec((0, self.E)), swiglu_limit=1e9))
        np.testing.assert_allclose(total, np.asarray(want), atol=2e-5)
        counts = np.concatenate([np.asarray(c) for _, c in parts])
        assert counts.sum() == self.K * lp["x"].shape[0]
        # the clamp is at work here: without it the sum is another
        assert np.abs(np.asarray(unclamped)
                      - np.asarray(want - ref.shared_part(
                          lp["x"], lp, self._spec((0, self.E))))).max() > 0.1

    def test_the_limit_stays_off_the_op_unless_asked(self):
        g = stf.Graph()
        with g.as_default():
            x = stf.zeros([4, 8])
            args = (x, stf.zeros([8, 16]), stf.zeros([4, 8, 8]),
                    stf.zeros([4, 4, 8]))
            plain, _ = moe_ops.routed_ffn_op(*args, top_k=2, held=(0, 4))
            clamped, _ = moe_ops.routed_ffn_op(*args, top_k=2, held=(0, 4),
                                               swiglu_limit=10.0)
        assert "swiglu_limit" not in plain.op.attrs
        assert clamped.op.attrs["swiglu_limit"] == 10.0


class TestServedModel:
    def test_two_kinds_of_pool_and_the_contract(self, served):
        model, config, cfg, mode = served
        if mode != "auto":
            pytest.skip("the graph is the same in both modes")
        assert model.state_outside_pages
        caches = [c for group in model._prog["caches"] for c in group]
        s_pools = [c for c in caches if not c.paged and len(c.shape) == 4]
        windows = [c for c in caches if not c.paged and len(c.shape) == 2]
        latent = [c for c in caches if c.paged and c.max_len == PAGE]
        pooled = [c for c in caches if c.paged and c.max_len == PAGE // 4]
        assert len(s_pools) == len(windows) == cfg.mixers.count("K") == 3
        assert s_pools[0].shape == (5, 4, 16, 16)
        assert windows[0].shape == (5, 3 * 3 * 64)
        assert latent[0].shape == (31, PAGE, 32)
        assert pooled[0].shape == (31, 2, 16)
        assert pooled[0].dtype.name == "float32"
        types = {op.type for op in model.graph.get_operations()}
        assert {"KDAChunkScan", "KDAStateUpdate", "PooledBlockChoice",
                "SparseLatentBlockAttention", "SelectedLatentAttention",
                "HyperConnectionPre", "HyperConnectionPost",
                "IndexerTopK"} <= types
        assert {"KDAChunkScan", "KDAStateUpdate"} <= set(
            kvc.STATE_UPDATE_OP_TYPES)

    @pytest.mark.parametrize("unsupported", ["int8", "mesh", "tp"])
    def test_paths_it_does_not_have_are_refused(self, unsupported):
        with pytest.raises(ValueError, match=unsupported):
            lm.HybridLinearMoEGenerativeModel(
                lm.HybridLinearMoEConfig.tiny(), page_len=8,
                init_fresh=True, **{unsupported: 2})

    def test_published_sizes(self):
        cfg = lm.HybridLinearMoEConfig()
        assert (cfg.mixers.count("K"), cfg.mixers.count("D")) == (34, 11)
        assert cfg.mixers[3::4] == "D" * 11 and cfg.mlps[:4] == "ddds"
        config, _ = _config()
        cut = config["program"]["config_kwargs"]
        assert (cut["mixers"], cut["mlps"]) == ("KDKK", "dsss")
        full = tiny_hybrid_linear_moe.tiny.load("configs",
                                                "glm-5.3-flash.json")
        kw = full["program"]["config_kwargs"]
        assert (kw["mixers"], kw["mlps"]) == (cfg.mixers[2:7], cfg.mlps[2:7])
        real = lm.HybridLinearMoEConfig(**kw)
        # 4 KDA layers x (64 x 128 x 128 x 4 B + 3 x 24,576 x 2 B)
        assert real.state_bytes_per_slot(2) == 4 * (4_194_304 + 147_456)
        assert real.page_bytes_per_token(2) == 1024 + 128


class TestTheOthersAreUnchanged:
    """The mHC op, the KDA and pooled-selection ops, the cache accessors and
    the SwiGLU limit leave the programs of the configurations the benchmark
    already has op for op as they were: the digests are of those graphs,
    every decode program with its last-token buffer (op types, input
    counts, attribute names and values, in order)."""

    KW = dict(page_len=8, pages_per_seq=6, num_pages=30, max_live=4,
              init_fresh=True, seed=0, compute_dtype=stf.float32,
              aot_warmup=False, prefill_bucket_sizes=[1, 2])

    def _build(self, which):
        from simple_tensorflow_tpu.models import causal_lm as cl
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
        return ss.StateSpaceMoEGenerativeModel(
            ss.StateSpaceMoEConfig.tiny(), **self.KW)

    @pytest.mark.parametrize("which,n_ops,digest", [
        ("lm-big", 480, "8c8e7b455a66303f"),
        ("sparse", 520, "f9cd679a69b37436"),
        ("latent", 720, "a539255010769d9a"),
        ("state_space", 672, "dcc6bd746c449066")])
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
        assert not {t for t, _, _ in sig} & {
            "HyperConnectionPre", "KDAChunkScan", "KDAStateUpdate",
            "PooledBlockChoice", "SelectedLatentAttention"}
