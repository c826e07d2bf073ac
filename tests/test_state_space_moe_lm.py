"""The hybrid state-space routed-FFN decoder (models/state_space_moe_lm.py)
on the CPU at tiny widths, in float32, against the benchmark's plain
reference (chipbench/reference/state_space_moe_decoder.py: the recurrence
token by token, no chunks, no cache, no slots, nothing of the program
imported): the ops, the two kinds of pool, the program contract (rows of
one call in the order given, padded tails, re-used slots), the chip's
share, and the engine."""

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
from chipbench.reference import state_space_moe_decoder as ref  # noqa: E402
from chipbench.runners import serve_state_space_moe as runner  # noqa: E402
from chipbench.tests import tiny_state_space_moe  # noqa: E402
from simple_tensorflow_tpu import serving  # noqa: E402
from simple_tensorflow_tpu.kernels import registry as kreg  # noqa: E402
from simple_tensorflow_tpu.models import state_space_moe_lm as lm  # noqa: E402
from simple_tensorflow_tpu.ops import kv_cache_ops as kvc  # noqa: E402
from simple_tensorflow_tpu.ops import moe_ops, ssm_ops  # noqa: E402
from simple_tensorflow_tpu.platform import monitoring  # noqa: E402

su = importlib.import_module(
    "simple_tensorflow_tpu.ops.pallas.ssm_state_update")
da = importlib.import_module(
    "simple_tensorflow_tpu.ops.pallas.decode_attention")

PAGE, PAGES_PER_SEQ, SEED = 8, 6, 20350001
LABEL = "tiny_state_space_moe"
# float32 against float32: the clean program reads 2e-6 on the worst logit
# (the chunked scan sums a block's terms in another order than the token
# scan); the float8 control reads 0.02 and more
TOL = 5e-5


def _config():
    """The benchmark's configuration file cut to tiny widths, float32 (the
    cut chipbench's own rehearsals use): MEM*EM, 16 experts of which this
    chip holds 8 (experts 4..11), top-4, chunk 4 (two blocks a page)."""
    config = tiny_state_space_moe.config("float32")
    return config, lm.StateSpaceMoEConfig(
        **config["program"]["config_kwargs"])


def _model(seed=SEED, **kw):
    config, cfg = _config()
    kw.setdefault("prefill_bucket_sizes", [1, 4])
    model = lm.StateSpaceMoEGenerativeModel(
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


def _rows(model, prompt, pages, slot):
    """The page-chunk rows of ``prompt[:-1]`` as the engine makes them:
    ``[(base, slot, page, tokens, real tokens)]`` and the prompt's table."""
    table = np.full((PAGES_PER_SEQ,), model.scratch_page, np.int32)
    table[:len(pages)] = pages
    n = -(-(len(prompt) - 1) // PAGE)
    body = np.full((n * PAGE,), model.pad_id, np.int32)
    body[:len(prompt) - 1] = prompt[:-1]
    rows = [(PAGE * c, slot, int(table[c]), body[PAGE * c:PAGE * (c + 1)],
             min(PAGE, len(prompt) - 1 - PAGE * c)) for c in range(n)]
    return rows, table


def _prefill_rows(model, rows, tables):
    """One ``prefill_chunk`` of ``rows`` in the order given."""
    if rows:
        model.prefill_chunk(
            [r[3] for r in rows], [r[0] for r in rows],
            [tables[r[1]] for r in rows], [r[2] for r in rows],
            slots=[r[1] for r in rows], lens=[r[4] for r in rows])


def _prefill(model, prompt, pages, slot, one_call=True):
    rows, table = _rows(model, prompt, pages, slot)
    for call in ([rows] if one_call else [[r] for r in rows]):
        _prefill_rows(model, call, {slot: table})
    return table[None]


def _decode_logits(model, tok, pos, tables, slot):
    _, p = model._decode_plans[1]
    feed = {p["tok"]: np.asarray([tok], np.int32),
            p["pos"]: np.asarray([pos], np.int32), p["tables"]: tables,
            p["dst"]: tables[:, pos // PAGE],
            p["off"]: np.asarray([pos % PAGE], np.int32),
            p["slots"]: np.asarray([slot], np.int32)}
    logits, nxt = model.session.run([p["logits"], p["next_tok"]], feed)
    return logits[0], int(nxt[0])


def _greedy(model, prompt, tables, slot, steps):
    tok, pos, got, toks = int(prompt[-1]), len(prompt) - 1, [], []
    for _ in range(steps):
        logits, tok = _decode_logits(model, tok, pos, tables, slot)
        got.append(logits)
        toks.append(tok)
        pos += 1
    return toks, np.stack(got)


def _reference_logits(spec, prompt, toks, seed=SEED, precision="f32"):
    seq = list(prompt) + list(toks)
    return np.asarray(ref.logits_at(
        spec, seed, [seq], [len(prompt) - 1 + np.arange(len(toks))],
        precision)[0])


@pytest.fixture(scope="module")
def decoded(served):
    """Prefill through both kinds of pool (4 page chunks as the rows of
    one call, the last one partial), then 12 decode positions."""
    model, config, cfg = served
    rng = np.random.default_rng(1)
    prompt = rng.integers(2, cfg.vocab_size, size=29).astype(np.int32)
    tables = _prefill(model, prompt, [3, 7, 11, 2, 9], slot=2)
    toks, got = _greedy(model, prompt, tables, 2, 12)
    return prompt, toks, got


class TestProgramAgainstReference:
    def test_prefill_then_decode_logits(self, served, decoded):
        """The chunked scan, the carried convolution window, the state
        update by slot and grouped-query attention over the paged K/V
        against the reference's full forward over prompt + served tokens,
        on logits."""
        _, config, _ = served
        prompt, toks, got = decoded
        want = _reference_logits(config["reference"]["spec"], prompt, toks)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
        assert toks == [int(t) for t in np.argmax(want, -1)]

    def test_the_float8_control_fails_the_tolerance(self, served, decoded):
        """Tight enough: the reference computed in float8 is 400x the
        tolerance away on the worst logit."""
        _, config, _ = served
        prompt, toks, got = decoded
        low = _reference_logits(config["reference"]["spec"], prompt, toks,
                                precision="fp8")
        assert np.abs(got - low).max() > 400 * TOL

    PLANTED = {
        "shared_expert": {
            "shared_part": lambda b, lp, precision="f32": jnp.zeros_like(b)},
        "scaling_factor": {"spec": {"gate_scale": 1.0}},
        "conv_bias": {"conv": lambda xbc, lp, _conv=ref.conv: _conv(
            xbc, dict(lp, conv_b=jnp.zeros_like(lp["conv_b"])))},
        "skip_term": {"recurrence": lambda x, dt, a, bm, cm, skip,
                      _r=ref.recurrence: _r(x, dt, a, bm, cm,
                                            jnp.zeros_like(skip))},
        "no_decay": {"recurrence": lambda x, dt, a, bm, cm, skip,
                     _r=ref.recurrence: _r(x, dt, jnp.zeros_like(a), bm, cm,
                                           skip)},
    }

    @pytest.mark.parametrize("omission", sorted(PLANTED))
    def test_planted_omission_moves_the_logits(self, served, decoded,
                                               monkeypatch, omission):
        """Each piece of the block left out of the reference moves the
        worst logit well past the tolerance: the test above would catch
        the program doing the same."""
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

    @pytest.mark.parametrize("extra", [-1, 1, PAGE // 2],
                             ids=["page-1", "page+1", "page+half"])
    def test_a_padded_tail_leaves_the_state_of_the_last_real_token(
            self, served, extra):
        """The cached span ``prompt[:-1]`` ends ``extra`` tokens past two
        whole pages: its last chunk is padded with pad_id, and the pad
        tokens must not run the recurrence on (``lens``)."""
        model, config, cfg = served
        rng = np.random.default_rng(10 + extra)
        prompt = rng.integers(2, cfg.vocab_size,
                              size=2 * PAGE + extra + 1).astype(np.int32)
        tables = _prefill(model, prompt, [5, 6, 8, 10], slot=1)
        toks, got = _greedy(model, prompt, tables, 1, 4)
        want = _reference_logits(config["reference"]["spec"], prompt, toks)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)

    def test_a_prompt_of_one_token_starts_from_zero_at_decode(self, served):
        """No prefill row at all: the first decode step is position 0 and
        must not read what the slot last held."""
        model, config, cfg = served
        stale = np.random.default_rng(3).integers(
            2, cfg.vocab_size, size=12).astype(np.int32)
        _prefill(model, stale, [1, 2], slot=3)
        prompt = np.asarray([17], np.int32)
        tables = np.full((1, PAGES_PER_SEQ), model.scratch_page, np.int32)
        tables[0, 0] = 4
        toks, got = _greedy(model, prompt, tables, 3, 3)
        want = _reference_logits(config["reference"]["spec"], prompt, toks)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


class TestRowsOfOneCall:
    """A prompt's chunks as the rows of one call = one call a chunk = two
    calls split anywhere, for two prompts of different lengths interleaved
    by ``(base, slot)`` as the engine's admission sorts them."""

    def _two_prompts(self, cfg):
        rng = np.random.default_rng(5)
        a = rng.integers(2, cfg.vocab_size, size=3 * PAGE + 4)
        b = rng.integers(2, cfg.vocab_size, size=PAGE + 6)
        return a.astype(np.int32), b.astype(np.int32)

    def _serve(self, model, cfg, cut):
        """Both prompts' rows sorted by (base, slot) and cut into calls at
        ``cut``; then 3 decode positions of each prompt."""
        a, b = self._two_prompts(cfg)
        rows_a, table_a = _rows(model, a, [3, 7, 11, 2], slot=0)
        rows_b, table_b = _rows(model, b, [14, 4], slot=2)
        tables = {0: table_a, 2: table_b}
        rows = sorted(rows_a + rows_b, key=lambda r: r[:2])
        assert [r[1] for r in rows] == [0, 2, 0, 2, 0, 0]   # interleaved
        for lo, hi in zip([0] + cut, cut + [len(rows)]):
            _prefill_rows(model, rows[lo:hi], tables)
        return [_greedy(model, p, tables[s][None], s, 3)[1]
                for p, s in ((a, 0), (b, 2))]

    @pytest.fixture(scope="class")
    def one_call_a_chunk(self):
        # bucket 1 only would change XLA's CPU tiling of the matmuls: every
        # variant below goes through buckets 1 and 4 of ONE model
        model, config, cfg = _model(prefill_bucket_sizes=[1, 4, 8])
        got = self._serve(model, cfg, [1, 2, 3, 4, 5])
        yield model, config, cfg, got
        model.close()

    def test_one_call_a_chunk_equals_the_reference(self, one_call_a_chunk):
        model, config, cfg, got = one_call_a_chunk
        for prompt, logits in zip(self._two_prompts(cfg), got):
            toks = [int(t) for t in np.argmax(logits, -1)]
            want = _reference_logits(config["reference"]["spec"], prompt,
                                     toks)
            np.testing.assert_allclose(logits, want, atol=TOL, rtol=0)

    @pytest.mark.parametrize("cut", [[], [1], [2], [3], [4], [5], [2, 4]],
                             ids=lambda c: "cut" + "_".join(map(str, c)))
    def test_rows_of_one_call_and_two_calls_split_anywhere(
            self, one_call_a_chunk, cut):
        """``[]`` is all six rows in ONE call (bucket 8): a row's entering
        state is what an earlier row of the same call left in the pool.
        The others split the rows into calls at every place."""
        model, _, cfg, want = one_call_a_chunk
        got = self._serve(model, cfg, cut)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=2e-6, rtol=0)


class TestOps:
    H, P, N, G = 8, 8, 16, 2

    def _inputs(self, rows, length, seed=0):
        ks = jax.random.split(jax.random.key(seed), 6)
        h, p, n, g = self.H, self.P, self.N, self.G
        lead = (rows, length) if length else (rows,)
        return {"x": jax.random.normal(ks[0], lead + (h, p)),
                "dt": jax.nn.softplus(jax.random.normal(ks[1], lead + (h,))),
                "a": -jnp.exp(jax.random.normal(ks[2], (h,))),
                "bm": jax.random.normal(ks[3], lead + (g, n)),
                "cm": jax.random.normal(ks[4], lead + (g, n)),
                "d": jax.random.normal(ks[5], (h,))}

    def _pool(self, slots, seed=9):
        inner = su.pool_inner_shape(self.H, self.P, self.N, self.G)
        return jax.random.normal(jax.random.key(seed), (slots,) + inner)

    def _token_scan(self, t, h0):
        """The reference's recurrence over one row, from state ``h0``."""
        k = self.H // self.G

        def step(h, s):
            x, dt, b, c = s
            h = (jnp.exp(dt * t["a"])[:, None, None] * h
                 + (dt[:, None] * x)[:, :, None] * b[:, None, :])
            return h, jnp.sum(h * c[:, None, :], -1) + t["d"][:, None] * x

        return jax.lax.scan(step, h0, (
            t["x"], t["dt"], jnp.repeat(t["bm"], k, 1),
            jnp.repeat(t["cm"], k, 1)))

    def test_state_update_kernel_equals_composition_equals_the_scan(self):
        """``SSMStateUpdate``: Pallas (interpret mode) = the gather-update-
        scatter composition = one token of the reference's scan; a fresh
        row starts from zero, the others from their slot."""
        t = self._inputs(5, 0)
        pool = self._pool(7)
        slots = jnp.asarray([4, 0, 6, 2, 5], jnp.int32)
        fresh = jnp.asarray([False, True, False, False, True])
        args = (t["x"], t["dt"], t["a"], t["bm"], t["cm"], t["d"], slots,
                fresh)
        y_k, pool_k = su.ssm_state_update(pool, *args)
        y_x, pool_x = su.ssm_state_update_xla(pool, *args)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_x),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(pool_k), np.asarray(pool_x),
                                   atol=1e-6)
        pack = su.state_pack(self.H, self.P, self.G)
        for i in range(5):
            h0 = jnp.where(fresh[i], 0.0,
                           su.from_pool_layout(pool[slots[i]], pack))
            one = {k: (v[i:i + 1] if v.ndim > 1 else v)
                   for k, v in t.items()}
            h1, y = self._token_scan(one, h0)
            np.testing.assert_allclose(np.asarray(y_x[i]), np.asarray(y[0]),
                                       atol=1e-5)
            np.testing.assert_allclose(
                np.asarray(su.from_pool_layout(pool_x[slots[i]], pack)),
                np.asarray(h1), atol=1e-5)
        # untouched slots keep what they held
        for s in (1, 3):
            np.testing.assert_array_equal(np.asarray(pool_k[s]),
                                          np.asarray(pool[s]))

    def test_pool_layout_round_trip_and_shapes(self):
        assert su.state_pack(64, 64, 8) == 2
        assert su.pool_inner_shape(64, 64, 128, 8) == (32, 128, 128)
        assert su.state_pack(8, 8, 2) == 4
        h = jax.random.normal(jax.random.key(0), (3, 8, 8, 16))
        packed = su.to_pool_layout(h, 4)
        assert packed.shape == (3, 2, 16, 32)
        np.testing.assert_array_equal(
            np.asarray(su.from_pool_layout(packed, 4)), np.asarray(h))
        # whole groups a block, within a megabyte, 8-row tiles
        assert su.packs_per_block(32, 4, 128, 128) == 16
        assert su.packs_per_block(2, 1, 16, 32) == 2

    @pytest.mark.parametrize("lens", [(256, 256), (256, 131), (1, 0)])
    def test_chunk_scan_at_chunk_128_equals_the_token_recurrence(self, lens):
        """Two rows of 256 tokens in blocks of 128, row 1 carrying on from
        what row 0 leaves in ITS slot (same slot, in order), against the
        token-by-token recurrence; past ``lens`` nothing moves."""
        t = self._inputs(2, 256, seed=2)
        t["dt"] = 0.1 * t["dt"]
        pool = self._pool(3)
        slots = jnp.asarray([1, 1], jnp.int32)
        fresh = jnp.asarray([True, False])
        y, new = ssm_ops.ssm_chunk_scan(
            pool, t["x"], t["dt"], t["a"], t["bm"], t["cm"], t["d"], slots,
            fresh, jnp.asarray(lens, jnp.int32), chunk=128)
        pack = su.state_pack(self.H, self.P, self.G)
        h = jnp.zeros((self.H, self.P, self.N))
        for i, n in enumerate(lens):
            row = {k: (v[i, :n] if v.ndim > 1 else v) for k, v in t.items()}
            if n:
                h, want = self._token_scan(row, h)
                np.testing.assert_allclose(np.asarray(y[i, :n]),
                                           np.asarray(want), atol=3e-4)
        np.testing.assert_allclose(
            np.asarray(su.from_pool_layout(new[1], pack)), np.asarray(h),
            atol=3e-4)
        np.testing.assert_array_equal(np.asarray(new[0]),
                                      np.asarray(pool[0]))

    def test_conv_chunk_equals_one_step_a_token_equals_the_reference(self):
        taps, c, length = 4, 12, 8
        ks = jax.random.split(jax.random.key(4), 3)
        x = jax.random.normal(ks[0], (1, 2 * length, c))
        lp = {"conv_w": jax.random.normal(ks[1], (taps, c)),
              "conv_b": jax.random.normal(ks[2], (c,))}
        want = np.asarray(ref.conv(x[0], lp))
        pool = jnp.ones((3, (taps - 1) * c))        # stale windows
        slot, yes, no = (jnp.asarray([1], jnp.int32), jnp.asarray([True]),
                         jnp.asarray([False]))
        # two chunk calls, the second carrying on from the pool
        y0, pool1 = ssm_ops.causal_conv1d(
            pool, x[:, :length], lp["conv_w"], lp["conv_b"], slot, yes,
            jnp.asarray([length], jnp.int32))
        y1, pool2 = ssm_ops.causal_conv1d(
            pool1, x[:, length:], lp["conv_w"], lp["conv_b"], slot, no,
            jnp.asarray([5], jnp.int32))
        np.testing.assert_allclose(np.asarray(y0[0]), want[:length],
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(y1[0]), want[length:],
                                   atol=1e-5)
        # the window behind the LAST REAL token (5 real of 8)
        np.testing.assert_allclose(
            np.asarray(pool2[1]).reshape(taps - 1, c),
            np.asarray(x[0, length + 2:length + 5]), atol=1e-6)
        # one token a call from the same state
        p, outs = pool1, []
        for j in range(5):
            y, p = ssm_ops.causal_conv1d(p, x[:, length + j], lp["conv_w"],
                                         lp["conv_b"], slot, no)
            outs.append(np.asarray(y[0]))
        np.testing.assert_allclose(np.stack(outs),
                                   want[length:length + 5], atol=1e-5)
        np.testing.assert_allclose(np.asarray(p[1]), np.asarray(pool2[1]),
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(pool2[0]),
                                      np.asarray(pool[0]))

    def test_gated_group_norm_gate_first(self):
        ks = jax.random.split(jax.random.key(6), 3)
        y, z = (jax.random.normal(k, (5, 32)) for k in ks[:2])
        gamma = 1.0 + 0.1 * jax.random.normal(ks[2], (32,))
        got = ssm_ops.gated_rms_norm(y, z, gamma, groups=4, eps=1e-5)
        g = (np.asarray(y) * np.asarray(jax.nn.silu(z))).reshape(5, 4, 8)
        want = g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(np.asarray(got),
                                   want.reshape(5, 32) * np.asarray(gamma),
                                   atol=1e-5)
        norm_first = (np.asarray(y).reshape(5, 4, 8) / np.sqrt(
            (np.asarray(y).reshape(5, 4, 8) ** 2).mean(-1, keepdims=True)
            + 1e-5)).reshape(5, 32) * np.asarray(jax.nn.silu(z) * gamma)
        assert np.abs(np.asarray(got) - norm_first).max() > 0.1

    def test_routing_of_the_state_update(self):
        assert "SSMStateUpdate" in kreg.kernel_types()
        key = kreg.aval_key(
            jax.ShapeDtypeStruct((256, 64, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((257, 32, 128, 128), jnp.float32),
            jax.ShapeDtypeStruct((256, 8, 128), jnp.bfloat16))
        kd = kreg._KERNELS["SSMStateUpdate"]
        assert kreg._route(kd, key, "auto", "cpu") == (
            "xla", "interpret_backend")
        assert kreg._route(kd, key, "auto", "tpu") == ("pallas",
                                                       "cost_model")
        assert kreg._route(kd, key, "off", "tpu") == ("xla", "mode_off")
        half = kreg.aval_key(
            jax.ShapeDtypeStruct((256, 64, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((257, 32, 128, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((256, 8, 128), jnp.bfloat16))
        assert kreg._route(kd, half, "force", "tpu") == (
            "xla", "ineligible_dtype")


class TestGroupedPagedAttention:
    @pytest.mark.parametrize("kq,h,h_kv,d", [
        (1, 8, 2, 16), (PAGE, 8, 2, 16), (1, 32, 2, 128), (16, 32, 2, 128),
        (1, 4, 4, 16)])
    def test_kernel_in_interpret_mode_equals_the_composition(self, kq, h,
                                                             h_kv, d):
        ks = jax.random.split(jax.random.key(kq + h), 3)
        rows, page_len, n_blocks, pages = 3, 16, 3, 7
        q = jax.random.normal(ks[0], (rows, kq, h, d) if kq > 1
                              else (rows, h, d))
        k_pool = jax.random.normal(ks[1], (pages, page_len, h_kv * d))
        v_pool = jax.random.normal(ks[2], (pages, page_len, h_kv * d))
        rng = np.random.default_rng(0)
        tables = jnp.asarray(rng.integers(0, pages, (rows, n_blocks)),
                             jnp.int32)
        lengths = jnp.asarray([1, 17, n_blocks * page_len - kq], jnp.int32)
        kw = dict(causal_offset=kq > 1)
        got = da.paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                        **kw)
        want = da.paged_decode_attention_xla(q, k_pool, v_pool, tables,
                                             lengths, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        # query head h reads key-value head h // rep: the composition over
        # the repeated heads is plain multi-head attention
        rep = h // h_kv

        def spread(pool):
            return jnp.repeat(pool.reshape(pages, page_len, h_kv, d), rep,
                              2).reshape(pages, page_len, h * d)

        plain = da.paged_decode_attention_xla(
            q, spread(k_pool), spread(v_pool), tables, lengths, **kw)
        np.testing.assert_allclose(np.asarray(want), np.asarray(plain),
                                   atol=1e-6)

    def test_tile_rule_and_routing(self):
        assert da.grouped_heads_per_tile(1, 16) == 16
        assert da.grouped_heads_per_tile(256, 16) == 4
        assert da.grouped_heads_per_tile(2048, 16) == 1
        kd = kreg._KERNELS["PagedDecodeAttention"]

        def key(h, d, lanes):
            return kreg.aval_key(
                jax.ShapeDtypeStruct((256, h, d), jnp.bfloat16),
                jax.ShapeDtypeStruct((3361, 256, lanes), jnp.bfloat16),
                jax.ShapeDtypeStruct((256, 13), jnp.int32))

        assert kreg._route(kd, key(32, 128, 256), "auto", "tpu")[0] == \
            "pallas"
        # a key-value head's lanes must be whole tiles to be sliced
        assert kreg._route(kd, key(8, 16, 32), "force", "tpu") == (
            "xla", "ineligible_shape")
        assert kreg._route(kd, key(16, 64, 1024), "auto", "tpu")[0] == \
            "pallas"
        assert kreg._route(kd, key(32, 128, 384), "force", "tpu") == (
            "xla", "ineligible_shape")


class TestChipsShare:
    E, K, H, W = 16, 4, 16, 8

    def _layer(self, t=24, seed=3):
        ks = jax.random.split(jax.random.key(seed), 7)
        h, e, w = self.H, self.E, self.W
        return {"x": jax.random.normal(ks[0], (t, h)),
                "wr": jax.random.normal(ks[1], (h, e)),
                "bias": 0.3 * jax.random.normal(ks[2], (e,)),
                "w_up": 0.3 * jax.random.normal(ks[3], (e, h, w)),
                "w_down": 0.3 * jax.random.normal(ks[4], (e, w, h)),
                "ws_up": 0.3 * jax.random.normal(ks[5], (h, 2 * w)),
                "ws_down": 0.3 * jax.random.normal(ks[6], (2 * w, h))}

    def _spec(self, held):
        return {"experts": self.E, "experts_per_token": self.K,
                "held": list(held), "gate_scale": 2.5, "norm_topk": True}

    def _part(self, lp, held):
        first, count = held
        return moe_ops.routed_ffn(
            lp["x"], lp["wr"], lp["w_up"][first:first + count],
            lp["w_down"][first:first + count], top_k=self.K,
            score="sigmoid", bias=lp["bias"], gate_scale=2.5, held=held,
            activation="relu2")

    def test_relu2_against_a_dense_loop(self):
        """``RoutedFFN(activation="relu2")``, uncut, against every expert
        applied to every token and weighted by the reference's gates."""
        lp = self._layer()
        with jax.default_matmul_precision("highest"):
            y, counts = moe_ops.routed_ffn(
                lp["x"], lp["wr"], lp["w_up"], lp["w_down"], top_k=self.K,
                score="sigmoid", bias=lp["bias"], gate_scale=2.5,
                activation="relu2")
            gates = ref.route(lp["x"], lp["wr"], lp["bias"],
                              self._spec((0, self.E)))
            dense = sum(
                gates[:, e, None] * (jnp.square(jax.nn.relu(
                    lp["x"] @ lp["w_up"][e])) @ lp["w_down"][e])
                for e in range(self.E))
        np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                                   atol=2e-5)
        assert int(counts.sum()) == self.K * lp["x"].shape[0]
        # and it is not the gated form: the up matrix has no gate half
        with pytest.raises(Exception):
            moe_ops.routed_ffn(lp["x"], lp["wr"], lp["w_up"], lp["w_down"],
                               top_k=self.K)

    def test_the_two_shares_add_up_to_the_uncut_layer(self):
        """Experts 0-7 and 8-15 (the deployment's two chips), with the
        shared expert counted ONCE, equal what the uncut reference gives
        for the whole layer."""
        lp = self._layer()
        with jax.default_matmul_precision("highest"):
            parts = [self._part(lp, (8 * k, 8)) for k in range(2)]
            total = sum(np.asarray(y) for y, _ in parts) + np.asarray(
                ref.shared_part(lp["x"], lp))
            want = (ref.routed_part(lp["x"], lp, self._spec((0, self.E)))
                    + ref.shared_part(lp["x"], lp))
        np.testing.assert_allclose(total, np.asarray(want), atol=2e-5)
        counts = np.concatenate([np.asarray(c) for _, c in parts])
        assert counts.sum() == self.K * lp["x"].shape[0]
        for k, (y, _) in enumerate(parts):
            cut = dict(lp, w_up=lp["w_up"][8 * k:8 * k + 8],
                       w_down=lp["w_down"][8 * k:8 * k + 8])
            with jax.default_matmul_precision("highest"):
                one = ref.routed_part(lp["x"], cut, self._spec((8 * k, 8)))
            np.testing.assert_allclose(np.asarray(y), np.asarray(one),
                                       atol=2e-5)

    @pytest.mark.parametrize("form", ["dense", "grouped"])
    def test_both_forms_of_the_held_call(self, monkeypatch, form):
        """A relu2 held call of few rows takes every held expert over
        every row (``moe_ops._held_dense``), a longer one the grouped
        matmul over the landed pairs: the same part of the sum, the same
        counts of the live rows."""
        monkeypatch.setattr(moe_ops, "_DENSE_MAX_ROWS",
                            512 if form == "dense" else 0)
        lp = self._layer(t=40)
        mask = jnp.arange(40) < 33
        held = (4, 8)
        with jax.default_matmul_precision("highest"):
            y, counts = moe_ops.routed_ffn(
                lp["x"], lp["wr"], lp["w_up"][4:12], lp["w_down"][4:12],
                mask, top_k=self.K, score="sigmoid", bias=lp["bias"],
                gate_scale=2.5, held=held, activation="relu2")
            cut = dict(lp, w_up=lp["w_up"][4:12], w_down=lp["w_down"][4:12])
            want = ref.routed_part(lp["x"], cut, self._spec(held))
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=2e-5)
        experts, _ = moe_ops.route(lp["x"], lp["wr"], top_k=self.K,
                                   norm_topk=True, score="sigmoid",
                                   bias=lp["bias"], gate_scale=2.5)
        live = np.asarray(experts)[:33]
        assert np.asarray(counts).tolist() == [
            int((live == e).sum()) for e in range(4, 12)]

    def test_a_call_of_one_token_takes_a_whole_tile_of_rows(self):
        # six pairs: 8 rows a pass, two of them padding
        assert moe_ops.held_window(6, 64, 128) == 8
        assert moe_ops.held_window(1536, 64, 128) == 1536
        assert moe_ops.held_window(256, 12, 384) == 16     # as it was

    def test_the_op_rejects_an_unknown_activation(self):
        with pytest.raises(ValueError, match="activation"):
            with stf.Graph().as_default():
                stf.nn.routed_ffn(
                    stf.zeros([2, 4]), stf.zeros([4, 4]),
                    stf.zeros([4, 4, 2]), stf.zeros([4, 2, 4]), top_k=2,
                    activation="gelu")


def _ops(model, op_type):
    return [op for op in model.graph.get_operations() if op.type == op_type]


def _server(model):
    server = serving.ModelServer()
    server.load_generative(model, LABEL, policy=serving.DecodePolicy(
        num_slots=model.num_slots, max_decode_len=model.max_seq_len,
        bucket_sizes=model.decode_buckets,
        prefill_bucket_sizes=model.prefill_buckets))
    return server


def _hits():
    return monitoring.get_metric(
        "/stf/serving/prefix_cache_hits").get_cell(LABEL).value()


class TestServed:
    def test_generate_a_reused_slot_and_the_same_prompt_twice(self):
        """ModelServer.generate end to end on ONE slot: A is served and
        retires, B takes A's slot and answers as B alone would (zero state
        at base 0); then B again: prefilled a second time (no trie hit;
        ``prefix_cache_hits`` stays 0) and answered alike."""
        model, config, cfg = _model(seed=SEED + 1)
        spec = config["reference"]["spec"]
        rng = np.random.default_rng(6)
        a = rng.integers(2, cfg.vocab_size, size=2 * PAGE + 5)
        b = rng.integers(2, cfg.vocab_size, size=PAGE + 3)
        hits = _hits()
        server = serving.ModelServer()
        server.load_generative(model, LABEL, policy=serving.DecodePolicy(
            num_slots=1, max_decode_len=model.max_seq_len, bucket_sizes=[1],
            prefill_bucket_sizes=model.prefill_buckets))
        real = monitoring.get_metric(
            "/stf/serving/prefill_real_tokens").get_cell(LABEL)
        pad = monitoring.get_metric(
            "/stf/serving/prefill_pad_tokens").get_cell(LABEL)
        before = real.value(), pad.value()
        try:
            answers = [server.generate(np.asarray(p, np.int32), model=LABEL,
                                       max_new_tokens=6).result(timeout=300)
                       for p in (a, b, b)]
            row = [r for r in server.statusz_info()
                   if r.get("model") == LABEL][0]
        finally:
            server.close()
        for prompt, ans in zip((a, b, b), answers):
            toks = [int(t) for t in ans["tokens"]]
            rows = ref.served_token_gaps(spec, SEED + 1, [prompt], [toks])[0]
            assert rows["gap"].max() < 1e-4, (rows["gap"], rows["margin"])
            assert np.abs(rows["logprob"]
                          - np.asarray(ans["logprobs"])).max() < 2e-4
        np.testing.assert_array_equal(answers[1]["tokens"],
                                      answers[2]["tokens"])
        np.testing.assert_allclose(answers[1]["logprobs"],
                                   answers[2]["logprobs"], atol=1e-6)
        # nothing was shared, everything was prefilled, every page freed
        assert _hits() == hits
        cache = row["prefix_cache"]
        assert cache["hit_pages"] == cache["cow_hits"] == 0
        assert cache["shared_pages"] == 0 and cache["free"] == 30
        assert cache["miss_pages"] == 3 + 2 + 2
        assert real.value() - before[0] == (len(a) - 1) + 2 * (len(b) - 1)
        assert pad.value() - before[1] == 7 * PAGE - (
            real.value() - before[0])

    def test_a_batch_of_prompts_through_the_engine(self):
        """Five prompts offered at once over four slots: admission hands
        their chunks over as rows sorted by (base, slot) with each row's
        slot and real length; the fifth waits for a slot another leaves."""
        model, config, cfg = _model(seed=SEED + 2)
        spec = config["reference"]["spec"]
        rng = np.random.default_rng(8)
        prompts = [rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
                   for n in (21, 9, 30, 1, 17)]
        server = _server(model)
        try:
            futures = [server.generate(p, model=LABEL, max_new_tokens=5)
                       for p in prompts]
            answers = [f.result(timeout=300) for f in futures]
        finally:
            server.close()
        for prompt, ans in zip(prompts, answers):
            toks = [int(t) for t in ans["tokens"]]
            rows = ref.served_token_gaps(spec, SEED + 2, [prompt], [toks])[0]
            assert rows["gap"].max() < 1e-4, (len(prompt), rows["gap"])

    def test_a_draft_is_refused_and_says_why(self, served):
        model, _, _ = served
        with pytest.raises(ValueError, match="rolled back"):
            serving.GenerativeEngine(
                "refused", model, serving.DecodePolicy(
                    num_slots=4, max_decode_len=model.max_seq_len,
                    bucket_sizes=model.decode_buckets), draft=object())

    def test_prefill_and_decode_need_the_slots(self, served):
        model, _, cfg = served
        tables = np.full((1, PAGES_PER_SEQ), model.scratch_page, np.int32)
        with pytest.raises(ValueError, match="slot"):
            model.prefill_chunk(np.zeros((1, PAGE), np.int32), [0], tables,
                                [1])
        with pytest.raises(ValueError, match="slot"):
            model.decode([5], [3], tables)

    def test_two_kinds_of_pool(self, served):
        model, _, cfg = served
        assert model.state_outside_pages
        caches = model._prog["caches"]
        kinds = [tuple(type(c).__name__ for c in group) for group in caches]
        assert kinds == [{"M": ("StatePool", "StatePool"), "E": (),
                          "*": ("KVCache", "KVCache")}[k]
                         for k in cfg.layer_kinds]
        h_pool, conv_pool = caches[0]
        assert h_pool.shape == (5,) + cfg.state_shape       # 4 slots + scratch
        assert h_pool.dtype == stf.float32
        assert conv_pool.shape == (5, (cfg.conv_kernel - 1) * cfg.conv_dim)
        assert caches[3][0].stored_shape == (
            31, PAGE, cfg.num_kv_heads * cfg.head_dim)
        # copy-on-write copies pages, not state; every pool is allocated
        assert len(_ops(model, "KVCachePageCopy")) == 2
        assert len(_ops(model, "StatePoolAlloc")) == 2 * 3
        # K and V of the one attention layer, and the last-token buffer
        assert len(_ops(model, "KVCacheAlloc")) == 2 + 1
        programs = len(model.decode_buckets) + len(model.prefill_buckets)
        assert len(_ops(model, "CausalConv1D")) == 3 * programs
        assert len(_ops(model, "SSMStateUpdate")) == 3 * len(
            model.decode_buckets)
        assert len(_ops(model, "SSMChunkScan")) == 3 * len(
            model.prefill_buckets)
        assert len(_ops(model, "PagedDecodeAttention")) == programs
        # no pool gathered: the one gather a decode program has reads its
        # rows' last tokens
        assert [op.attrs["var_name"] for op in _ops(model, "KVCacheGather")
                ] == ["causal_lm_pg/last_tok"] * len(model.decode_buckets)
        routed = _ops(model, "RoutedFFN")
        assert len(routed) == 2 * programs
        assert all(op.attrs["activation"] == "relu2"
                   and op.attrs["held"] == (4, 8)
                   and op.attrs["score"] == "sigmoid" for op in routed)
        total, _ = model._cache_bytes()
        assert total == (30 * PAGE * cfg.kv_bytes_per_token(4)
                         + 5 * cfg.state_bytes_per_slot(4))
        feeds = {op.name for op in _ops(model, "Placeholder")}
        assert {"lm_prefill4_slots", "lm_prefill4_lens",
                "lm_decode4_slots"} <= feeds
        assert "lm_decode4_lens" not in feeds

    def test_serving_lint_accepts_the_state_pools(self, served):
        from simple_tensorflow_tpu import analysis

        model, _, _ = served
        _, p = model._decode_plans[1]
        with model.graph.as_default():
            diags = analysis.lint_graph(
                model.graph, fetches=[p["next_tok"], p["logp"]],
                purpose="serving", rules=["lint/serving-decode-cache"])
        assert not [d for d in diags if d.severity == "error"], diags
        assert all(kvc.is_cache_op(op) for t in kvc.STATE_UPDATE_OP_TYPES
                   for op in _ops(model, t))

    def test_a_fetched_state_pool_is_a_lint_error(self):
        from simple_tensorflow_tpu import analysis

        with stf.Graph().as_default() as g:
            pool = kvc.state_pool("lint_pool", 3, (2, 4, 8), stf.float32)
            leaked = pool.alloc()
            diags = analysis.lint_graph(
                g, fetches=[leaked], purpose="serving",
                rules=["lint/serving-decode-cache"])
        assert any("fetched" in d.message for d in diags
                   if d.severity == "error"), diags

    def test_cost_model_prices_the_state_update_by_its_rows(self, served):
        from simple_tensorflow_tpu.framework import cost_model

        model, _, cfg = served
        op = _ops(model, "SSMStateUpdate")[-1]              # bucket 4
        rows = int(op.inputs[0].shape[0])
        state = cfg.mamba_num_heads * cfg.mamba_head_dim * cfg.ssm_state_size
        assert cost_model._op_flops(op, 0) == 6.0 * rows * state
        # a row's state read and written once, never the whole pool
        assert cost_model._op_bytes_dispatch(op) < 2.5 * rows * state * 4 \
            + 1e5

    def test_step_counters(self, served):
        model, _, cfg = served
        cells = {name: monitoring.get_metric(
            "/stf/serving/" + name).get_cell(LABEL)
            for name in ("state_bytes_share", "moe_local_pair_share",
                         "moe_load_imbalance", "decode_live_page_share")}
        before = {k: c.value()["count"] for k, c in cells.items()}
        share_sum = cells["state_bytes_share"].value()["sum"]
        tables = np.full((2, PAGES_PER_SEQ), model.scratch_page, np.int32)
        tables[:, :3] = [[1, 2, 3], [4, 5, 6]]
        model.decode([5, 6], [15, 23], tables, slots=[0, 1])
        after = {k: c.value() for k, c in cells.items()}
        for name in ("state_bytes_share", "moe_local_pair_share",
                     "decode_live_page_share"):
            assert after[name]["count"] == before[name] + 1
        # 2 rows' state over that plus (2 + 3) live pages of K/V
        state = 2 * cfg.state_bytes_per_slot(4)
        pages = 5 * PAGE * cfg.kv_bytes_per_token(4)
        assert after["state_bytes_share"]["sum"] - share_sum == \
            pytest.approx(state / (state + pages))
        assert monitoring.get_metric(
            "/stf/serving/state_pool_bytes").get_cell(LABEL).value() == \
            5 * cfg.state_bytes_per_slot(4)

    @pytest.mark.parametrize("unsupported", ["int8", "mesh", "tp"])
    def test_paths_it_does_not_have_are_refused(self, unsupported):
        with pytest.raises(ValueError, match=unsupported):
            lm.StateSpaceMoEGenerativeModel(
                lm.StateSpaceMoEConfig.tiny(), init_fresh=True,
                **{unsupported: True})

    def test_published_sizes(self):
        cfg = lm.StateSpaceMoEConfig()
        assert cfg.num_layers == 52
        assert [cfg.layer_pattern.count(k) for k in "ME*"] == [23, 23, 6]
        assert cfg.d_inner == 4096 and cfg.conv_dim == 6144
        assert cfg.state_shape == (32, 128, 128)
        first = dataclasses.replace(cfg, layer_pattern="MEMEM*EMEMEM*")
        assert first.state_bytes_per_slot(2) == 6 * (2_097_152 + 36_864)
        assert first.kv_bytes_per_token(2) == 2_048


class TestTheOthersAreUnchanged:
    """The state pools, the ``slots`` and ``lens`` feeds and the layer-kind
    loop leave the programs of the configurations the benchmark already
    has op for op as they were: the digests are of those graphs, every
    decode program with its last-token buffer and its ``slots`` feed (op
    types, input counts and attribute names, in order)."""

    KW = dict(page_len=8, pages_per_seq=6, num_pages=30, max_live=4,
              init_fresh=True, seed=0, compute_dtype=stf.float32,
              aot_warmup=False, prefill_bucket_sizes=[1, 2])

    def _build(self, which):
        from simple_tensorflow_tpu.models import causal_lm as cl
        from simple_tensorflow_tpu.models import latent_moe_lm as la_lm
        from simple_tensorflow_tpu.models import sparse_moe_lm as sm
        from simple_tensorflow_tpu.models.transformer import (
            TransformerConfig)

        if which == "lm-big":
            return cl.CausalLMGenerativeModel(dataclasses.replace(
                TransformerConfig.tiny(), max_len=64), **self.KW)
        if which == "sparse":
            return sm.SparseMoEGenerativeModel(sm.SparseMoEConfig.tiny(),
                                               **self.KW)
        return la_lm.LatentMoEGenerativeModel(la_lm.LatentMoEConfig.tiny(),
                                              **self.KW)

    @pytest.mark.parametrize("which,n_ops,digest", [
        ("lm-big", 480, "8dc98ae8532894f1"),
        ("sparse", 520, "4d5e386280568f64"),
        ("latent", 720, "9bfe69b9a304a764")])
    def test_op_list_as_before(self, which, n_ops, digest):
        model = self._build(which)
        try:
            ops = model.graph.get_operations()
            sig = [(op.type, len(op.inputs), tuple(sorted(
                k for k in op.attrs if not k.startswith("_src"))))
                for op in ops]
            feeds = {op.name for op in ops if op.type == "Placeholder"}
            assert not model.state_outside_pages
        finally:
            model.close()
        assert len(ops) == n_ops
        assert hashlib.sha256(repr(sig).encode()).hexdigest()[:16] == digest
        # every decode program takes slots (its last-token buffer's
        # rows); only a stack with state by slot gives prefill slots, lens
        assert not [f for f in feeds if "prefill" in f
                    and ("slots" in f or "lens" in f)]
        assert not [t for t, _, _ in sig if t in kvc.STATE_UPDATE_OP_TYPES
                    or t == "StatePoolAlloc"]
