"""Hybrid decoder-only LM — Mamba-2 state-space layers, a few grouped-query
attention layers and a sigmoid-routed relu² feed-forward with a shared
expert, ONE mixer a layer — served through the paged programs of
``models/causal_lm.py`` as ONE CHIP'S SHARE of a deployment that divides
every routed layer's experts over several chips.

(ref: none — the block of recent open hybrid state-space mixture-of-experts
decoders.) ``layer_pattern`` names each layer's kind; every layer is ``x <-
x + mixer(RMSNorm(x))``, and after the last an RMSNorm and an UNTIED head.

- ``M``, Mamba-2. ``d_inner = mamba_num_heads x mamba_head_dim``, ``G =
  n_groups``, ``N = ssm_state_size``, conv width ``d_inner + 2 G N``. ``[z
  | xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC))``, a causal depthwise
  convolution of ``conv_kernel`` taps with bias; ``[x | B | C] = xBC``
  (``heads / G`` heads a group); ``dt <- softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a scalar a head; per head ``h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``; ``y <-
  RMSNorm_groups(y * silu(z)) * gamma`` over ``G`` groups (the gate first,
  then the norm); ``out = y W_out``. **A sequence's state is ``h`` (heads x
  head_dim x N, float32) and the last ``conv_kernel - 1`` rows of ``xBC``:
  no row a position, so not in any page.** It lives in two pools a layer
  addressed by SLOT (``kv_cache_ops.StatePool``).
- ``E``, routed FFN. ``s = sigmoid(x W_r)`` in float32; the
  ``experts_per_token`` experts of largest ``s + bias`` (the bias chooses,
  it does not weigh; a tie to the lower expert); gates ``s`` over their sum
  x ``routed_scaling_factor``; an expert is ``relu(x W_up)^2 W_down`` — no
  gate matrix (stored ``expert_width_stored`` wide, zero-padded to whole
  lane tiles); plus a shared expert of the same form, ungated.
- ``*``, attention. ``num_heads`` query / ``num_kv_heads`` key-value heads
  x ``head_dim``, no bias, ``softmax(q k^T / sqrt(head_dim))`` causal, NO
  rotary or other position embedding; K and V in paged caches under the
  page table, read in place (``PagedDecodeAttention``, grouped queries).

THE SHARE. ``held_experts = (first, count)`` and ``vocab_size`` as
``models/latent_moe_lm.py`` has them: this chip's experts' part of every
routed sum, the shared expert added once, a slice of the vocabulary;
attention and the state-space layers whole.

THE PROGRAM CONTRACT. The stack's ``layer_caches`` names state pools, so
``build_paged_lm_program`` gives both programs a ``slots`` feed and prefill
a ``lens`` feed, and the engine — which has had a slot for every sequence
all along — hands them over (``serving/generative.py``). A prefill call's
rows are walked in the order given by every ``M`` layer (``ops/ssm_ops.py``:
a row of ``base`` 0 starts from zero; a padded tail leaves the state of the
last real token). What such a model may NOT use: prefix-cache hits (a trie
hit hands over K/V pages whose state nobody kept) and a draft model (a
rejected proposal's state cannot be rolled back); the engine sees
``state_outside_pages`` and shares nothing and refuses a draft.
Serving only: there is no training graph for this block (ROADMAP X0).
"""

from __future__ import annotations

import dataclasses

import numpy as np

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu.models.causal_lm import (
    CausalLMGenerativeModel, PreNormStack, build_paged_lm_program)
from simple_tensorflow_tpu.models.latent_moe_lm import (
    sample_held_expert_counts)
from simple_tensorflow_tpu.ops.pallas.ssm_state_update import (
    pool_inner_shape)
from simple_tensorflow_tpu.platform import monitoring

_state_pool_bytes = monitoring.IntGauge(
    "/stf/serving/state_pool_bytes",
    "Bytes of the pools of per-sequence state addressed by slot (all "
    "layers, the scratch slot included)", "model")
_state_bytes_share = monitoring.Sampler(
    "/stf/serving/state_bytes_share",
    monitoring.ExponentialBuckets(0.01, 1.5, 12),
    "Per decode step: the live rows' slot-pool state bytes over those plus "
    "the bytes of their live K/V pages: the share of a step's per-sequence "
    "state that no position addresses", "model")


@dataclasses.dataclass
class StateSpaceMoEConfig:
    vocab_size: int = 131072
    d_model: int = 2688
    # one character a layer: M state-space, E routed FFN, * attention
    layer_pattern: str = ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                          "EMEMEMEME")
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    num_experts: int = 128
    experts_per_token: int = 6
    expert_width: int = 1856
    shared_width: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    # (first, count) of the experts this chip holds; None = all of them
    held_experts: tuple | None = None
    max_len: int = 262144
    pad_id: int = 0
    eos_id: int = 1

    @staticmethod
    def tiny():
        return StateSpaceMoEConfig(
            vocab_size=96, d_model=64, layer_pattern="MEM*EM",
            num_heads=8, num_kv_heads=2, head_dim=16, mamba_num_heads=8,
            mamba_head_dim=8, ssm_state_size=16, n_groups=2, chunk_size=4,
            num_experts=16, experts_per_token=4, expert_width=32,
            shared_width=48, held_experts=(4, 8), max_len=64)

    @property
    def layer_kinds(self):
        return tuple(self.layer_pattern)

    @property
    def num_layers(self):
        return len(self.layer_pattern)

    @property
    def held(self):
        return tuple(self.held_experts or (0, self.num_experts))

    @property
    def expert_width_stored(self):
        """Width the routed experts' matrices are STORED at: whole
        128-lane tiles, the columns past ``expert_width`` zero (``relu(0)^2
        = 0``: the same function). The grouped matmul takes a ``(E, H,
        I)`` operand whose ``I`` is no whole number of tiles only through
        a relaid copy of ALL of it, every call: 660 MB a layer at 64 x
        2688 x 1856 (described-chip compile, PR 35); the tiled layout pads
        1856 to 1920 lanes anyway."""
        return -(-self.expert_width // 128) * 128

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def state_shape(self):
        """Inner shape of a layer's state pool, as stored."""
        return pool_inner_shape(self.mamba_num_heads, self.mamba_head_dim,
                                self.ssm_state_size, self.n_groups)

    def state_bytes_per_slot(self, compute_dtype_size):
        """One sequence's state over all ``M`` layers: float32 ``h`` and
        the convolution's carried window in the compute dtype."""
        per_layer = (4 * int(np.prod(self.state_shape))
                     + (self.conv_kernel - 1) * self.conv_dim
                     * compute_dtype_size)
        return self.layer_pattern.count("M") * per_layer

    def kv_bytes_per_token(self, compute_dtype_size):
        return (self.layer_pattern.count("*") * 2 * self.num_kv_heads
                * self.head_dim * compute_dtype_size)


class _StateSpaceMoEStack(PreNormStack):
    """The block stack as ``build_paged_lm_program`` sees one: one mixer a
    layer, by ``cfg.layer_kinds``."""

    keeps_state = True

    def layer_caches(self, kvc, total_pages, page_len, sharding,
                     state_slots):
        cfg = self.cfg

        def of(i, kind):
            name = f"{self.scope}_pg/l{i}_"
            if kind == "M":
                return (kvc.state_pool(name + "h", state_slots,
                                       cfg.state_shape, stf.float32),
                        kvc.state_pool(name + "conv", state_slots,
                                       ((cfg.conv_kernel - 1) * cfg.conv_dim,),
                                       self.compute_dtype))
            if kind == "*":
                return tuple(
                    kvc.kv_cache(name + which, total_pages, page_len,
                                 (cfg.num_kv_heads, cfg.head_dim),
                                 self.compute_dtype, sharding=sharding,
                                 paged=True) for which in "kv")
            return ()

        return [of(i, kind) for i, kind in enumerate(cfg.layer_kinds)]

    # -- the three mixers -------------------------------------------------
    def _mamba(self, i, u, rows, lead, cache, block):
        cfg = self.cfg
        d, di = cfg.d_model, cfg.d_inner
        h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
        g, n, taps = cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel
        f32 = stf.float32

        def vec(name, shape, init):
            return stf.get_variable(name, shape, dtype=f32, initializer=init)

        z, xbc, dt = stf.split(
            stf.matmul(u, self._w("mamba/in", [d, di + cfg.conv_dim + h], d)),
            [di, cfg.conv_dim, h], axis=-1)
        dt = stf.nn.softplus(stf.cast(dt, f32) + vec(
            "mamba/dt_bias", [h], stf.zeros_initializer()))
        a = -stf.exp(vec("mamba/A_log", [h], stf.zeros_initializer()))
        skip = vec("mamba/D", [h], stf.ones_initializer())
        conv_w = self._w("mamba/conv_w", [taps, cfg.conv_dim], taps)
        conv_b = vec("mamba/conv_b", [cfg.conv_dim], stf.zeros_initializer())
        h_pool, conv_pool = cache.state(i)
        slots, fresh = cache.slots, cache.fresh()
        # block: (B, S, ...) with the rows' real lengths; else (B, ...)
        shape = lead if block else lead[:1]
        lens = (cache.lens,) if block else ()
        xbc = stf.nn.silu(stf.nn.causal_conv1d(
            stf.reshape(xbc, shape + [cfg.conv_dim]), conv_w, conv_b,
            conv_pool, slots, fresh, *lens))
        x, bm, cm = stf.split(xbc, [di, g * n, g * n], axis=-1)
        x = stf.reshape(x, shape + [h, p])
        bm, cm = (stf.reshape(t, shape + [g, n]) for t in (bm, cm))
        dt = stf.reshape(dt, shape + [h])
        if block:
            y = stf.nn.ssm_chunk_scan(x, dt, a, bm, cm, skip, h_pool, slots,
                                      fresh, cache.lens,
                                      chunk=cfg.chunk_size)
        else:
            y = stf.nn.ssm_state_update(x, dt, a, bm, cm, skip, h_pool,
                                        slots, fresh)
        y = stf.nn.gated_rms_norm(
            stf.reshape(y, [rows, di]), z,
            stf.get_variable("mamba/norm", [di], dtype=f32,
                             initializer=stf.ones_initializer()),
            groups=g, eps=cfg.rms_norm_eps)
        return stf.matmul(y, self._w("mamba/out", [di, d], di))

    def _attention(self, i, u, rows, lead, cache, block):
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.head_dim
        width = cfg.num_heads * hd

        def heads(name, count):
            return stf.reshape(
                stf.matmul(u, self._w(name, [d, count * hd], d)),
                lead + [count, hd])

        q = heads("attn/q", cfg.num_heads)
        k, v = heads("attn/k", cfg.num_kv_heads), heads("attn/v",
                                                        cfg.num_kv_heads)
        if block:
            o = cache.attend_block(i, q, k, v)
        else:                       # (B, 1, H, D): one position a row
            o = cache.attend(i, stf.reshape(q, [rows, cfg.num_heads, hd]),
                             k, v)
        return stf.matmul(stf.reshape(o, [rows, width]),
                          self._w("attn/out", [width, d], width))

    def _relu2(self, b, name, width):
        d = self.cfg.d_model
        up = stf.matmul(b, self._w(f"{name}up", [d, width], d))
        return stf.matmul(stf.square(stf.nn.relu(up)),
                          self._w(f"{name}down", [width, d], width))

    def _moe(self, i, x, row_mask):
        cfg = self.cfg
        d, e, width = cfg.d_model, cfg.num_experts, cfg.expert_width_stored
        held = cfg.held
        b = self._norm(x, "norm", d, out_dtype="float32")
        y, counts = stf.nn.routed_ffn(
            b, self._w("moe/router", [d, e], d, dtype=stf.float32),
            self._w("moe/up", [held[1], d, width], d),
            self._w("moe/down", [held[1], width, d], width),
            row_mask, top_k=cfg.experts_per_token,
            norm_topk=cfg.norm_topk_prob, score="sigmoid",
            bias=stf.get_variable(
                "moe/bias", [e], dtype=stf.float32,
                initializer=stf.random_normal_initializer(stddev=0.01)),
            gate_scale=cfg.routed_scaling_factor, held=held,
            activation="relu2")
        # the shared expert: once, whatever is held here
        shared = self._relu2(stf.cast(b, self.compute_dtype), "moe/shared_",
                             cfg.shared_width)
        return stf.cast(y + stf.cast(shared, stf.float32),
                        self.compute_dtype), counts

    def _layer(self, kind, i, x, rows, lead, positions, attend, row_mask):
        cache, block = attend
        if kind == "E":
            y, counts = self._moe(i, x, row_mask)
            return x + y, counts
        u = self._norm(x, "norm", self.cfg.d_model)
        mixer = {"M": self._mamba, "*": self._attention}.get(kind)
        if mixer is None:
            raise ValueError(f"unknown layer kind {kind!r} in "
                             f"{self.cfg.layer_pattern!r}")
        return x + mixer(i, u, rows, lead, cache, block), None

    # -- the two programs -----------------------------------------------------
    def prefill_block(self, tok, base, cache):
        b, s = int(tok.shape[0]), int(tok.shape[1])
        with stf.variable_scope(self.scope, reuse=stf.AUTO_REUSE):
            x = stf.reshape(self._embed(tok), [b * s, self.cfg.d_model])
            x, _ = self._layers(x, b * s, [b, s], None, (cache, True))
        return x

    def decode_step(self, tok, pos, cache):
        b = int(tok.shape[0])
        with stf.variable_scope(self.scope, reuse=stf.AUTO_REUSE):
            x, counts = self._layers(self._embed(tok), b, [b, 1], None,
                                     (cache, False),
                                     row_mask=cache.live_rows())
            logits = self._logits(x)
        # (a pattern without a routed layer has no histogram to return)
        return logits, ({"expert_counts": stf.stack(counts)} if counts
                        else {})


class StateSpaceMoEGenerativeModel(CausalLMGenerativeModel):
    """Session-owning paged serving programs of the hybrid state-space
    routed-FFN decoder; the engine-facing half (``prefill_chunk``,
    ``decode``, buckets, page geometry) is
    :class:`CausalLMGenerativeModel`'s — with ``slots`` and ``lens``, since
    ``state_outside_pages`` — the block stack is this module's.

    ``metrics_label`` (the base class's) labels the gauge
    ``/stf/serving/state_pool_bytes`` and the per-step samplers:
    ``state_bytes_share``, ``moe_load_imbalance`` over the HELD experts,
    ``moe_local_pair_share`` and ``decode_live_page_share``.
    """

    def __init__(self, cfg: StateSpaceMoEConfig, *, pages_per_seq=4, **kw):
        for unsupported in ("int8", "mesh", "tp"):
            if kw.get(unsupported):
                raise ValueError(f"{type(self).__name__} has no "
                                 f"{unsupported}= path")
        super().__init__(cfg, pages_per_seq=pages_per_seq, **kw)
        _state_pool_bytes.get_cell(self._metrics_label).set(
            self._state_pool_bytes())

    def _state_pool_bytes(self):
        return (self.num_slots + 1) * self.cfg.state_bytes_per_slot(
            self._compute_dtype.size)

    def _cache_bytes(self):
        """Both kinds of pool: K/V pages and state by slot."""
        pages = (self.cfg.kv_bytes_per_token(self._compute_dtype.size)
                 * self.num_pages * self.page_len)
        total = pages + self._state_pool_bytes()
        return total, total

    def _build_program(self, *, compute_dtype, scope, tp_axis, **kw):
        return build_paged_lm_program(
            _StateSpaceMoEStack(self.cfg, compute_dtype, scope),
            compute_dtype=compute_dtype, scope=scope,
            max_live=self.num_slots, **kw)

    def _after_decode(self, out, n, positions):
        super()._after_decode(out, n, positions)       # live page share
        cfg, size = self.cfg, self._compute_dtype.size
        live_pages = -(-(np.asarray(positions[:n], np.int64) + 1)
                       // self.page_len)
        state = n * cfg.state_bytes_per_slot(size)
        pages = (live_pages.sum() * self.page_len
                 * cfg.kv_bytes_per_token(size))
        _state_bytes_share.get_cell(self._metrics_label).add(
            float(state / (state + pages)))
        if "expert_counts" in out:
            sample_held_expert_counts(self._metrics_label,
                                      out["expert_counts"], n,
                                      cfg.experts_per_token)
