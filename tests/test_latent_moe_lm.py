"""The latent-attention routed-FFN decoder (models/latent_moe_lm.py) on the
CPU at tiny widths, in float32, against the benchmark's plain reference
(chipbench/reference/latent_moe_decoder.py: the PLAIN attention form, a
loop over the held experts, nothing of the program imported): the ops, the
paged programs, the chip's share, and the engine."""

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
from chipbench.reference import latent_moe_decoder as ref  # noqa: E402
from chipbench.runners import serve_latent_moe as runner  # noqa: E402
from chipbench.tests import tiny_latent_moe  # noqa: E402
from simple_tensorflow_tpu import serving  # noqa: E402
from simple_tensorflow_tpu.kernels import registry as kreg  # noqa: E402
from simple_tensorflow_tpu.models import latent_moe_lm as lm  # noqa: E402
from simple_tensorflow_tpu.ops import moe_ops  # noqa: E402
from simple_tensorflow_tpu.ops import sparse_attention_ops as sa  # noqa: E402
from simple_tensorflow_tpu.platform import monitoring  # noqa: E402

la = importlib.import_module(
    "simple_tensorflow_tpu.ops.pallas.latent_attention")

PAGE, PAGES_PER_SEQ, SEED = 8, 6, 20330001
LABEL = "tiny_latent_moe"
# float32 against float32: the clean program reads 2e-6 on the worst logit
TOL = 5e-5


def _config():
    """The benchmark's configuration file cut to tiny widths, float32
    (the cut chipbench's own rehearsals use): 3 layers (1 dense), 16
    experts of which this chip holds 8 (experts 4..11), top-4."""
    config = tiny_latent_moe.config("float32")
    return config, lm.LatentMoEConfig(**config["program"]["config_kwargs"])


def _model(seed=SEED, **kw):
    config, cfg = _config()
    kw.setdefault("prefill_bucket_sizes", [1, 2])
    model = lm.LatentMoEGenerativeModel(
        cfg, page_len=PAGE, pages_per_seq=PAGES_PER_SEQ, num_pages=30,
        max_live=4, init_fresh=True, seed=0, compute_dtype=stf.float32,
        metrics_label=LABEL, **kw)
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
    rows are a full call and a call of one row and three pad rows.
    Bit-equal decode logits, both ways through the one bucket: XLA's CPU
    matmuls tile by the row count, and this stack's buckets 1 and 4 part
    in the last place (2e-6) whatever the rows hold."""
    model, _, cfg = _model(prefill_bucket_sizes=[4], aot_warmup=False)
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


@pytest.fixture(scope="module")
def decoded(served):
    """Prefill through the paged latent cache (4 page chunks, the last one
    partial), then 12 decode positions (contexts 29..40): the prompt, the
    greedy tokens, and the logits of every decode position."""
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
    return prompt, toks, np.stack(got)


def _reference_logits(spec, prompt, toks):
    seq = list(prompt) + list(toks)
    return np.asarray(ref.logits_at(
        spec, SEED, [seq], [len(prompt) - 1 + np.arange(len(toks))])[0])


class TestProgramAgainstReference:
    def test_prefill_then_decode_logits(self, served, decoded):
        """The ABSORBED form through the paged latent cache (both
        programs) against the reference's PLAIN form over prompt + served
        tokens, on logits."""
        _, config, _ = served
        prompt, toks, got = decoded
        want = _reference_logits(config["reference"]["spec"], prompt, toks)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
        assert toks == [int(t) for t in np.argmax(want, -1)]

    def _route_with(weigh_with_bias, choose_with_bias):
        def route(b, wr, bias, spec):
            s = jax.nn.sigmoid(jnp.dot(b, wr, precision=ref._HI))
            _, top_e = jax.lax.top_k(s + bias if choose_with_bias else s,
                                     spec["experts_per_token"])
            top_s = jnp.take_along_axis(s + bias if weigh_with_bias else s,
                                        top_e, axis=-1)
            top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
            rows = jnp.arange(b.shape[0])[:, None]
            return jnp.zeros_like(s).at[rows, top_e].set(
                top_s * spec["gate_scale"])
        return route

    def _no_latent_norm(x, g, eps, _norm=ref._rms_norm):
        return x if g.shape[0] == 24 else _norm(x, g, eps)   # kv_rank

    def _unrotated_shared_key(x, positions, spec, _rope=ref._rope):
        # the one rope key all heads share (a single head) left out
        return jnp.zeros_like(x) if x.shape[1] == 1 else _rope(
            x, positions, spec)

    def _plain_frequencies(spec):
        dim = spec["qk_rope_dim"]
        return (spec["rope_theta"] ** (-jnp.arange(0, dim, 2,
                                                   dtype=jnp.float32) / dim),
                1.0)

    PLANTED = {
        "bias_used_as_a_weight": {"route": _route_with(True, True)},
        "bias_left_out_of_the_choice": {"route": _route_with(False, False)},
        "scaling_factor": {"spec": {"gate_scale": 1.0}},
        "shared_expert": {
            "shared_part": lambda b, lp, precision="f32": jnp.zeros_like(b)},
        "m_squared": {"softmax_scale": lambda spec: (
            spec["qk_nope_dim"] + spec["qk_rope_dim"]) ** -0.5},
        "latent_norm": {"_rms_norm": _no_latent_norm},
        "shared_rope_key": {"_rope": _unrotated_shared_key},
        "yarn_ramp": {"yarn_inv_freq": _plain_frequencies},
    }

    @pytest.mark.parametrize("omission", sorted(PLANTED))
    def test_planted_omission_moves_the_logits(self, served, decoded,
                                               monkeypatch, omission):
        """Each piece of the block left out of (or twisted in) the
        reference moves the worst logit well past the tolerance: the test
        above would catch the program doing the same."""
        _, config, _ = served
        prompt, toks, got = decoded
        planted = dict(self.PLANTED[omission])
        spec = dict(config["reference"]["spec"], **planted.pop("spec", {}))
        for name, fn in planted.items():
            monkeypatch.setattr(ref, name, fn)
        jax.clear_caches()           # the reference's layers are jitted
        try:
            want = _reference_logits(spec, prompt, toks)
        finally:
            monkeypatch.undo()
            jax.clear_caches()
        assert np.abs(got - want).max() > 100 * TOL, omission

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


class TestLatentAttention:
    def _case(self, kq, seed=7, heads=4, nope=16, rope=8, rank=24, v=16,
              width=128):
        """A pool of latent rows [c_kv ; k_r ; 0], three sequences of
        ragged lengths over it — rows 0 and 2 SHARE physical page 3 — and
        per-head plain-form weights."""
        rng = np.random.default_rng(seed)
        pool = np.zeros((9, PAGE, width), np.float32)
        pool[:, :, :rank + rope] = rng.normal(size=(9, PAGE, rank + rope))
        tables = np.array([[3, 1, 4, 8], [2, 5, 8, 8], [3, 6, 7, 0]],
                          np.int32)
        lengths = np.array([16, 8, 24] if kq > 1 else [17, 9, 30], np.int32)
        shape = (3, kq, heads) if kq > 1 else (3, heads)
        q_nope = rng.normal(size=shape + (nope,)).astype(np.float32)
        q_rope = rng.normal(size=shape + (rope,)).astype(np.float32)
        w_k = rng.normal(size=(rank, heads, nope)).astype(np.float32)
        w_v = rng.normal(size=(rank, heads, v)).astype(np.float32)
        return pool, tables, lengths, q_nope, q_rope, w_k, w_v

    @staticmethod
    def _absorbed_q(q_nope, q_rope, w_k, width):
        q_lat = np.einsum("...hn,chn->...hc", q_nope, w_k)
        q = np.concatenate([q_lat, q_rope], -1)
        return np.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, width - q.shape[-1])])

    @pytest.mark.parametrize("kq", [1, PAGE], ids=["decode", "block"])
    def test_kernel_in_interpret_mode_equals_the_composition(self, kq):
        pool, tables, lengths, q_nope, q_rope, w_k, _ = self._case(kq)
        q = self._absorbed_q(q_nope, q_rope, w_k, pool.shape[-1])
        kw = dict(value_dim=24, sm_scale=0.3, causal_offset=kq > 1)
        got = la.paged_latent_attention(q, pool, tables, lengths, **kw)
        want = la.paged_latent_attention_xla(q, pool, tables, lengths, **kw)
        assert got.shape == q.shape[:-1] + (24,)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @pytest.mark.parametrize("kq", [1, PAGE], ids=["decode", "block"])
    def test_absorbed_equals_plain(self, kq):
        """``u_h . W^V_h`` of the absorbed form over the cache rows equals
        per-head attention over keys and values up-projected from them."""
        pool, tables, lengths, q_nope, q_rope, w_k, w_v = self._case(kq)
        rank, rope = 24, 8
        q = self._absorbed_q(q_nope, q_rope, w_k, pool.shape[-1])
        u = np.asarray(la.paged_latent_attention_xla(
            q, pool, tables, lengths, value_dim=rank, sm_scale=0.3,
            causal_offset=kq > 1))
        got = np.einsum("...hc,chv->...hv", u, w_v)
        view = pool[tables].reshape(3, -1, pool.shape[-1])
        c_kv, k_r = view[..., :rank], view[..., rank:rank + rope]
        k_nope = np.einsum("blc,chn->blhn", c_kv, w_k)
        value = np.einsum("blc,chv->blhv", c_kv, w_v)
        qn = q_nope if kq > 1 else q_nope[:, None]
        qr = q_rope if kq > 1 else q_rope[:, None]
        s = 0.3 * (np.einsum("bqhn,blhn->bqhl", qn, k_nope)
                   + np.einsum("bqhr,blr->bqhl", qr, k_r))
        span = np.arange(view.shape[1])[None, None, None, :]
        allowed = lengths[:, None, None, None] + (
            1 + np.arange(kq)[None, :, None, None] if kq > 1 else 0)
        s = np.where(span < allowed, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("bqhl,blhv->bqhv", p, value)
        np.testing.assert_allclose(got, want if kq > 1 else want[:, 0],
                                   atol=2e-5)

    def test_routing_and_tile_rule(self):
        assert "PagedLatentAttention" in kreg.kernel_types()
        key = kreg.aval_key(
            jax.ShapeDtypeStruct((32, 64, 640), jnp.bfloat16),
            jax.ShapeDtypeStruct((1201, 512, 640), jnp.bfloat16),
            jax.ShapeDtypeStruct((32, 37), jnp.int32), value_dim=512)
        kd = kreg._KERNELS["PagedLatentAttention"]
        assert kreg._route(kd, key, "auto", "cpu") == (
            "xla", "interpret_backend")
        assert kreg._route(kd, key, "auto", "tpu")[0] == "pallas"
        assert kreg._route(kd, key, "force", "cpu") == ("pallas", "forced")
        assert kreg._route(kd, key, "off", "tpu") == ("xla", "mode_off")
        assert kreg._route(kd, key, "auto", "tpu", True) == (
            "xla", "mesh_auto_partitioned")
        mixed = kreg.aval_key(
            jax.ShapeDtypeStruct((32, 64, 640), jnp.bfloat16),
            jax.ShapeDtypeStruct((1201, 512, 640), jnp.float32),
            jax.ShapeDtypeStruct((32, 37), jnp.int32), value_dim=512)
        assert kreg._route(kd, mixed, "force", "tpu") == (
            "xla", "ineligible_dtype")
        # all 64 heads in one tile at a decode step, one head at a
        # 512-query block
        assert la.latent_heads_per_tile(1, 64) == 64
        assert la.latent_heads_per_tile(512, 64) == 1
        assert la.latent_heads_per_tile(8, 4) == 4

    def test_programs_through_the_kernel_equal_the_composition(self, served,
                                                               decoded):
        """The whole model once more with every PagedLatentAttention
        routed to the Pallas kernel (interpret mode): same logits."""
        _, _, cfg = served
        prompt, toks, got = decoded
        stf.kernels.set_mode("force")
        try:
            model, _, _ = _model()
        finally:
            stf.kernels.set_mode(None)
        try:
            tables = _prefill(model, prompt, [3, 7, 11, 2, 9])
            tok, pos, forced = int(prompt[-1]), len(prompt) - 1, []
            for _ in range(4):
                logits, tok = _decode_logits(model, tok, pos, tables)
                forced.append(logits)
                pos += 1
        finally:
            model.close()
        np.testing.assert_allclose(np.stack(forced), got[:4], atol=TOL)


class TestYarn:
    def test_published_ramp_bounds(self):
        """theta 50000, 64 rope dims, original length 4096, beta 32 / 1:
        the ramp runs over frequency pairs 8..20."""
        freq = np.asarray(sa.yarn_inv_freq(
            64, 50000.0, factor=64.0, original_len=4096, beta_fast=32.0,
            beta_slow=1.0))
        plain = 50000.0 ** (-np.arange(0, 64, 2) / 64)
        np.testing.assert_allclose(freq[:9], plain[:9], rtol=1e-6)
        np.testing.assert_allclose(freq[20:], plain[20:] / 64, rtol=1e-6)
        assert (freq[9:20] < plain[9:20]).all()
        assert (freq[9:20] > plain[9:20] / 64).all()
        np.testing.assert_allclose(
            lm.LatentMoEConfig().softmax_scale, 0.144680, rtol=1e-5)
        assert lm.LatentMoEConfig().rope_amplitude == 1.0
        assert lm.LatentMoEConfig().latent_row == 640

    def test_op_equals_the_reference(self):
        config, cfg = _config()
        spec = config["reference"]["spec"]
        x = jax.random.normal(jax.random.key(0), (5, 3, 8))
        pos = jnp.asarray([0, 3, 17, 40, 63])
        got = sa.rotary_embedding(x, pos, theta=cfg.rope_theta,
                                  yarn=cfg.yarn,
                                  amplitude=cfg.rope_amplitude)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref._rope(x, pos, spec)),
                                   atol=1e-6)
        plain = sa.rotary_embedding(x, pos, theta=cfg.rope_theta)
        assert np.abs(np.asarray(plain) - np.asarray(got)).max() > 0.1


class TestChipsShare:
    E, K, H, W = 16, 4, 16, 8

    def _layer(self, t=24, seed=3):
        ks = jax.random.split(jax.random.key(seed), 7)
        h, e, w = self.H, self.E, self.W
        return {"x": jax.random.normal(ks[0], (t, h)),
                "wr": jax.random.normal(ks[1], (h, e)),
                "bias": 0.3 * jax.random.normal(ks[2], (e,)),
                "w_gate_up": 0.3 * jax.random.normal(ks[3], (e, h, 2 * w)),
                "w_down": 0.3 * jax.random.normal(ks[4], (e, w, h)),
                "ws_gate_up": 0.3 * jax.random.normal(ks[5], (h, 2 * w)),
                "ws_down": 0.3 * jax.random.normal(ks[6], (w, h))}

    def _spec(self, held):
        return {"experts": self.E, "experts_per_token": self.K,
                "held": list(held), "gate_scale": 2.5, "norm_topk": True}

    def _part(self, lp, held, x=None):
        first, count = held
        return moe_ops.routed_ffn(
            lp["x"] if x is None else x, lp["wr"],
            lp["w_gate_up"][first:first + count],
            lp["w_down"][first:first + count], top_k=self.K,
            score="sigmoid", bias=lp["bias"], gate_scale=2.5, held=held)

    def test_the_parts_add_up_to_the_uncut_layer(self):
        """16 experts over 4 shares: the four chips' parts of the routed
        sum, plus the shared expert counted ONCE, equal what the uncut
        reference gives for the whole layer."""
        lp = self._layer()
        with jax.default_matmul_precision("highest"):
            parts = [self._part(lp, (4 * k, 4)) for k in range(4)]
            total = sum(np.asarray(y) for y, _ in parts) + np.asarray(
                ref.shared_part(lp["x"], lp))
            want = (ref.routed_part(lp["x"], lp, self._spec((0, self.E)))
                    + ref.shared_part(lp["x"], lp))
        np.testing.assert_allclose(total, np.asarray(want), atol=2e-5)
        counts = np.concatenate([np.asarray(c) for _, c in parts])
        assert counts.shape == (self.E,)
        assert counts.sum() == self.K * lp["x"].shape[0]
        # and each part is the reference's part for the same share
        for k, (y, _) in enumerate(parts):
            held = (4 * k, 4)
            cut = dict(lp, w_gate_up=lp["w_gate_up"][4 * k:4 * k + 4],
                       w_down=lp["w_down"][4 * k:4 * k + 4])
            with jax.default_matmul_precision("highest"):
                one = ref.routed_part(lp["x"], cut, self._spec(held))
            np.testing.assert_allclose(np.asarray(y), np.asarray(one),
                                       atol=2e-5)

    def test_every_pair_lands_here_the_second_pass(self):
        """A router that sends every token's 4 experts into the held
        range: 96 pairs land where an even router sends 24, the window is
        48 rows, so the loop makes a second pass and drops nothing."""
        lp = self._layer()
        held = (4, 4)
        wr = lp["wr"].at[:, 4:8].set(0.0)
        lp = dict(lp, wr=jnp.zeros_like(wr), bias=jnp.where(
            (jnp.arange(self.E) >= 4) & (jnp.arange(self.E) < 8), 5.0, 0.0))
        pairs = self.K * lp["x"].shape[0]
        assert moe_ops.held_window(pairs, 4, self.E) == 48 < pairs
        with jax.default_matmul_precision("highest"):
            y, counts = self._part(lp, held)
            cut = dict(lp, w_gate_up=lp["w_gate_up"][4:8],
                       w_down=lp["w_down"][4:8])
            want = ref.routed_part(lp["x"], cut, self._spec(held))
        assert np.asarray(counts).tolist() == [24, 24, 24, 24]
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=2e-5)

    def test_no_pair_lands_here(self):
        lp = self._layer()
        lp = dict(lp, wr=jnp.zeros_like(lp["wr"]), bias=jnp.where(
            jnp.arange(self.E) < 4, 5.0, 0.0))
        y, counts = self._part(lp, (8, 4))
        assert not np.asarray(y).any() and not np.asarray(counts).any()

    def test_row_mask_and_the_bias_that_only_chooses(self):
        lp = self._layer(t=8)
        mask = jnp.arange(8) < 5
        first, count = held = (4, 8)
        y_all, _ = self._part(lp, held)
        y, counts = moe_ops.routed_ffn(
            lp["x"], lp["wr"], lp["w_gate_up"][first:first + count],
            lp["w_down"][first:first + count], mask, top_k=self.K,
            score="sigmoid", bias=lp["bias"], gate_scale=2.5, held=held)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_all))
        experts, gates = moe_ops.route(
            lp["x"], lp["wr"], top_k=self.K, norm_topk=True,
            score="sigmoid", bias=lp["bias"], gate_scale=2.5)
        live = np.asarray(experts)[:5]
        assert int(counts.sum()) == ((live >= 4) & (live < 12)).sum()
        # the gates are the sigmoid scores normalised: they sum to the
        # scale whatever the bias chose
        np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-5)
        plain, _ = moe_ops.route(lp["x"], lp["wr"], top_k=self.K,
                                 norm_topk=True, score="sigmoid")
        assert (np.asarray(plain) != np.asarray(experts)).any()


def _ops(model, op_type):
    return [op for op in model.graph.get_operations() if op.type == op_type]


class TestServed:
    def test_generate_with_cow_over_the_latent_cache(self):
        """ModelServer.generate end to end: B's cached span ends inside
        A's second page, so its tail page is a copy of A's latent rows;
        both answers equal the reference's greedy tokens."""
        model, config, cfg = _model(seed=SEED + 1)
        spec = config["reference"]["spec"]
        rng = np.random.default_rng(6)
        base = rng.integers(2, cfg.vocab_size, size=2 * PAGE + 1).tolist()
        prompt_b = base[:PAGE + 3] + [int(rng.integers(2, cfg.vocab_size))]
        server = serving.ModelServer()
        server.load_generative(model, LABEL, policy=serving.DecodePolicy(
            num_slots=4, max_decode_len=model.max_seq_len,
            bucket_sizes=model.decode_buckets,
            prefill_bucket_sizes=model.prefill_buckets))
        try:
            answers = [server.generate(np.asarray(p, np.int32), model=LABEL,
                                       max_new_tokens=6).result(timeout=300)
                       for p in (base, prompt_b)]
            row = [r for r in server.statusz_info()
                   if r.get("model") == LABEL][0]
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

    def test_one_latent_cache_a_layer_copied_on_write(self, served):
        model, _, cfg = served
        caches = model._prog["caches"]
        assert len(caches) == cfg.num_layers
        assert all(len(group) == 1 for group in caches)
        assert caches[0][0].stored_shape == (31, PAGE, cfg.latent_row)
        assert cfg.latent_row == 128      # 24 + 8 padded to a lane tile
        assert len(_ops(model, "KVCachePageCopy")) == cfg.num_layers
        # decode and prefill programs read the pool in place: no gather
        # but of the decode rows' last tokens
        assert [op.attrs["var_name"] for op in _ops(model, "KVCacheGather")
                ] == ["causal_lm_pg/last_tok"] * len(model.decode_buckets)
        assert len(_ops(model, "PagedLatentAttention")) == cfg.num_layers * (
            len(model.decode_buckets) + len(model.prefill_buckets))

    def test_two_kinds_of_layer(self, served):
        model, _, cfg = served
        routed = _ops(model, "RoutedFFN")
        assert len(routed) == (cfg.num_layers - cfg.dense_layers) * (
            len(model.decode_buckets) + len(model.prefill_buckets))
        assert all(op.attrs["held"] == (4, 8)
                   and op.attrs["score"] == "sigmoid"
                   and op.attrs["has_bias"] for op in routed)
        names = {v.name.split(":")[0] for v in model.graph.get_collection(
            "trainable_variables")}
        assert "causal_lm/decoder/layer_0/ffn/gate_up" in names
        assert "causal_lm/decoder/layer_0/moe/router" not in names
        assert "causal_lm/decoder/layer_1/moe/shared_gate_up" in names
        assert "causal_lm/decoder/layer_1/ffn/gate_up" not in names

    def test_serving_lint_accepts_the_latent_cache(self, served):
        from simple_tensorflow_tpu import analysis

        model, _, _ = served
        _, p = model._decode_plans[1]
        with model.graph.as_default():
            diags = analysis.lint_graph(
                model.graph, fetches=[p["next_tok"], p["logp"]],
                purpose="serving", rules=["lint/serving-decode-cache"])
        assert not [d for d in diags if d.severity == "error"], diags

    def test_step_counters(self, served):
        model, _, cfg = served
        cells = {name: monitoring.get_metric(
            "/stf/serving/" + name).get_cell(LABEL)
            for name in ("moe_local_pair_share", "moe_load_imbalance",
                         "decode_live_page_share")}
        before = {k: c.value()["count"] for k, c in cells.items()}
        tables = np.full((2, PAGES_PER_SEQ), model.scratch_page, np.int32)
        tables[:, :3] = [[1, 2, 3], [4, 5, 6]]
        model.decode([5, 6], [15, 23], tables)
        after = {k: c.value() for k, c in cells.items()}
        assert after["moe_local_pair_share"]["count"] == \
            before["moe_local_pair_share"] + 1
        assert after["decode_live_page_share"]["count"] == \
            before["decode_live_page_share"] + 1
        # 8 of 16 experts held: about half of the pairs, never more than all
        assert 0.0 <= after["moe_local_pair_share"]["max"] <= 1.0
        assert after["moe_local_pair_share"]["sum"] > 0.0
        # (2 + 3) live pages of 2 rows x 6 entries
        assert after["decode_live_page_share"]["max"] >= 5 / 12 - 1e-9

    def test_expert_counts_are_of_the_live_rows_and_the_held_experts(
            self, served):
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
        assert counts.shape == (cfg.num_layers - cfg.dense_layers,
                                cfg.held[1])
        assert (counts.sum(axis=-1) <= 2 * cfg.experts_per_token).all()

    @pytest.mark.parametrize("unsupported", ["int8", "mesh", "tp"])
    def test_paths_it_does_not_have_are_refused(self, unsupported):
        with pytest.raises(ValueError, match=unsupported):
            lm.LatentMoEGenerativeModel(
                lm.LatentMoEConfig.tiny(), init_fresh=True,
                **{unsupported: True})


class TestTheOthersAreUnchanged:
    """The new attributes' defaults leave the graphs of the
    configurations the benchmark already has as they were."""

    def test_sparse_moe_graph(self):
        from simple_tensorflow_tpu.models import sparse_moe_lm as sm

        model = sm.SparseMoEGenerativeModel(
            sm.SparseMoEConfig.tiny(), page_len=PAGE,
            pages_per_seq=PAGES_PER_SEQ, num_pages=30, max_live=4,
            init_fresh=True, seed=0, compute_dtype=stf.float32,
            aot_warmup=False)
        try:
            routed, rope = _ops(model, "RoutedFFN"), _ops(
                model, "RotaryEmbedding")
            assert routed and rope
            assert all(set(op.attrs) == {"top_k", "norm_topk"}
                       for op in routed)
            assert all(set(op.attrs) == {"theta"} for op in rope)
            assert not _ops(model, "PagedLatentAttention")
            assert {len(op.inputs) for op in routed} == {4, 5}
        finally:
            model.close()

    def test_dense_lm_graph(self):
        from simple_tensorflow_tpu.models import causal_lm
        from simple_tensorflow_tpu.models.transformer import (
            TransformerConfig)

        model = causal_lm.CausalLMGenerativeModel(
            TransformerConfig.tiny(), page_len=4, pages_per_seq=4,
            num_pages=12, max_live=3, init_fresh=True, seed=11,
            aot_warmup=False)
        try:
            assert _ops(model, "PagedDecodeAttention")
            assert not _ops(model, "PagedLatentAttention")
            assert not _ops(model, "RoutedFFN")
        finally:
            model.close()

    def test_default_routing_is_the_softmax_block(self):
        """``route`` with the defaults is the softmax router it was:
        probabilities, top-k, renormalised."""
        ks = jax.random.split(jax.random.key(9), 2)
        x = jax.random.normal(ks[0], (6, 16))
        wr = jax.random.normal(ks[1], (16, 8))
        experts, gates = moe_ops.route(x, wr, top_k=2, norm_topk=True)
        p = jax.nn.softmax(jnp.dot(x, wr, precision="highest"), axis=-1)
        top_p, top_e = jax.lax.top_k(p, 2)
        np.testing.assert_array_equal(np.asarray(experts), np.asarray(top_e))
        np.testing.assert_allclose(
            np.asarray(gates),
            np.asarray(top_p / top_p.sum(-1, keepdims=True)), atol=1e-6)
