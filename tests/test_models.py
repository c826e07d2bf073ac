"""Model zoo smoke tests (tiny configs; mirrors ref model tutorials)."""

import numpy as np
import pytest

import simple_tensorflow_tpu as stf


@pytest.fixture(autouse=True)
def fresh_graph():
    stf.reset_default_graph()
    yield


def test_mnist_softmax_trains():
    from simple_tensorflow_tpu.models import mnist

    m = mnist.softmax_model(learning_rate=0.01)
    rng = np.random.RandomState(0)
    images = rng.rand(256, 784).astype(np.float32)
    w_true = rng.randn(784, 10).astype(np.float32)
    labels = np.argmax(images @ w_true, axis=1)
    onehot = np.zeros((256, 10), np.float32)
    onehot[np.arange(256), labels] = 1.0
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        first = None
        for _ in range(50):
            _, l = sess.run([m["train_op"], m["loss"]],
                            feed_dict={m["x"]: images, m["y_"]: onehot})
            if first is None:
                first = l
        assert l < first * 0.7


def test_mnist_convnet_trains():
    from simple_tensorflow_tpu.models import mnist

    m = mnist.convnet_model(batch_size=16)
    rng = np.random.RandomState(0)
    images = rng.rand(16, 28, 28, 1).astype(np.float32)
    labels = rng.randint(0, 10, 16).astype(np.int32)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        losses = []
        for _ in range(10):
            _, l = sess.run([m["train_op"], m["loss"]],
                            feed_dict={m["x"]: images, m["y_"]: labels,
                                       m["keep_prob"]: 0.9})
            losses.append(float(l))
        assert losses[-1] < losses[0]
        assert int(np.asarray(sess.run(m["global_step"]))) == 10


def test_resnet_tiny_forward_and_step():
    from simple_tensorflow_tpu.models import resnet

    # batch 4 / 64px keeps late-stage BN statistics sane (batch 2 at 1x1
    # spatial degenerates BN variance and legitimately explodes gradients)
    m = resnet.resnet50_train_model(batch_size=4, image_size=64,
                                    num_classes=10, dtype=stf.float32,
                                    learning_rate=1e-2)
    images, labels = resnet.synthetic_imagenet(4, 64)
    labels = labels % 10
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        _, l1 = sess.run([m["train_op"], m["loss"]],
                         feed_dict={m["images"]: images,
                                    m["labels"]: labels})
        _, l2 = sess.run([m["train_op"], m["loss"]],
                         feed_dict={m["images"]: images,
                                    m["labels"]: labels})
        assert np.isfinite(l1) and np.isfinite(l2)
        assert l2 < l1 * 10  # sanity: not exploding


def test_bert_tiny_trains():
    from simple_tensorflow_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    m = bert.bert_pretrain_model(batch_size=4, seq_len=16, max_predictions=4,
                                 cfg=cfg, compute_dtype=stf.float32,
                                 learning_rate=1e-3)
    batch = bert.synthetic_pretrain_batch(4, 16, 4, vocab_size=cfg.vocab_size)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        feed = {m[k]: v for k, v in batch.items()}
        l0 = sess.run(m["loss"], feed)
        for _ in range(10):
            _, l = sess.run([m["train_op"], m["loss"]], feed)
        assert np.isfinite(l) and l < l0


def test_bert_with_input_mask():
    from simple_tensorflow_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    m = bert.bert_pretrain_model(batch_size=2, seq_len=16, max_predictions=4,
                                 cfg=cfg, compute_dtype=stf.float32,
                                 use_input_mask=True)
    batch = bert.synthetic_pretrain_batch(2, 16, 4, vocab_size=cfg.vocab_size)
    batch["input_mask"] = np.concatenate(
        [np.ones((2, 12), np.int32), np.zeros((2, 4), np.int32)], axis=1)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        l = sess.run(m["loss"], {m[k]: v for k, v in batch.items()})
        assert np.isfinite(l)


def test_bert_pretrain_config_lowers_to_flash_attention():
    """The HEADLINE config — padded batches AND attention dropout — must
    run the Pallas flash kernel, not an XLA fallback."""
    from simple_tensorflow_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    cfg.attention_dropout = 0.1  # the pretraining setting
    cfg.hidden_dropout = 0.1
    m = bert.bert_pretrain_model(batch_size=2, seq_len=16, max_predictions=4,
                                 cfg=cfg, compute_dtype=stf.float32,
                                 use_input_mask=True)
    g = stf.get_default_graph()
    flash_ops = [op for op in g.get_operations()
                 if op.type in ("FlashAttention", "FlashAttentionDropout")]
    assert len(flash_ops) == cfg.num_layers, [op.type for op in flash_ops]
    # training graph with dropout -> the stateful dropout variant, with the
    # padding bias wired as a 4th input
    assert all(op.type == "FlashAttentionDropout" for op in flash_ops)
    assert all(len(op.inputs) == 4 for op in flash_ops)
    # and the whole thing trains
    batch = bert.synthetic_pretrain_batch(2, 16, 4, vocab_size=cfg.vocab_size)
    batch["input_mask"] = np.concatenate(
        [np.ones((2, 12), np.int32), np.zeros((2, 4), np.int32)], axis=1)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        feed = {m[k]: v for k, v in batch.items()}
        l0 = sess.run(m["loss"], feed)
        for _ in range(5):
            _, l = sess.run([m["train_op"], m["loss"]], feed)
        assert np.isfinite(l)
        # dropout masks must differ between runs (stateful RNG stream):
        # two loss evals in different runs may differ, but training should
        # still make progress on average
        assert l < l0 * 1.5


def test_transformer_tiny_trains():
    from simple_tensorflow_tpu.models import transformer as tr

    cfg = tr.TransformerConfig.tiny()
    m = tr.transformer_train_model(batch_size=4, src_len=8, tgt_len=8,
                                   cfg=cfg, compute_dtype=stf.float32)
    batch = tr.synthetic_wmt_batch(4, 8, 8, vocab_size=cfg.vocab_size)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        feed = {m[k]: v for k, v in batch.items() if k in m}
        l0 = sess.run(m["loss"], feed)
        for _ in range(15):
            _, l = sess.run([m["train_op"], m["loss"]], feed)
        assert np.isfinite(l) and l < l0


def test_transformer_beam_search():
    from simple_tensorflow_tpu.models import transformer as tr

    cfg = tr.TransformerConfig.tiny()
    src = stf.placeholder(stf.int32, [2, 8], "src")
    # default bf16 compute dtype: the decode logits are bf16 and the beam
    # scoring must cast up itself (regression: f32 one_hot * bf16 logits
    # was a strict-dtype TypeError)
    ids, scores = tr.beam_search_decode(src, cfg, beam_size=3, decode_len=8,
                                        compute_dtype=stf.bfloat16)
    batch = tr.synthetic_wmt_batch(2, 8, 8, vocab_size=cfg.vocab_size)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        out_ids, out_scores = sess.run([ids, scores],
                                       {src: batch["src_ids"]})
    assert out_ids.shape == (2, 3, 8)
    assert out_scores.shape == (2, 3)
    assert (out_ids[:, :, 0] == cfg.eos_id).all()
    # beams sorted by score
    assert (np.diff(out_scores, axis=1) <= 1e-5).all()


def test_word2vec_trains():
    from simple_tensorflow_tpu.models import word2vec as w2v

    m = w2v.skipgram_model(vocab_size=100, embedding_size=16, batch_size=8,
                           num_sampled=4, learning_rate=0.5)
    xi, yi = w2v.synthetic_skipgram_batch(8, 100)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        feed = {m["train_inputs"]: xi, m["train_labels"]: yi}
        l0 = sess.run(m["loss"], feed)
        for _ in range(20):
            _, l = sess.run([m["train_op"], m["loss"]], feed)
        assert l < l0
        sim = w2v.similarity(m["normalized_embeddings"], [1, 2, 3])
        assert sess.run(sim).shape == (3, 100)


def test_rnn_seq2seq_trains_and_decodes():
    from simple_tensorflow_tpu.models import rnn_seq2seq as s2s

    cfg = s2s.Seq2SeqConfig.tiny()
    m = s2s.seq2seq_model(8, cfg)
    src, lens, ti, to = s2s.synthetic_copy_batch(8, cfg, seed=1)
    feed = {m["src"]: src, m["src_len"]: lens, m["tgt_in"]: ti,
            m["tgt_out"]: to}
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        l0 = float(np.asarray(sess.run(m["loss"], feed)))
        for _ in range(60):
            sess.run(m["train_op"], feed)
        l1 = float(np.asarray(sess.run(m["loss"], feed)))
        assert l1 < l0 * 0.5, (l0, l1)
        dec = np.asarray(sess.run(m["decoded"], feed))
    assert dec.shape == (8, cfg.tgt_len)
    # the copy task is learnable to high accuracy even in 60 steps
    msk = to > 0
    assert (dec[msk] == to[msk]).mean() > 0.5


def test_long_context_lm_on_sp_mesh():
    from simple_tensorflow_tpu import parallel
    from simple_tensorflow_tpu.models import long_context as lc

    cfg = lc.LongContextConfig.tiny()
    with parallel.Mesh({"dp": 2, "sp": 4}):
        m = lc.lm_train_model(batch_size=2, seq_len=32, cfg=cfg,
                              compute_dtype=stf.float32)
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            feed_ids, feed_tg = lc.synthetic_lm_batch(2, 32, cfg.vocab_size)
            feed = {m["input_ids"]: feed_ids, m["targets"]: feed_tg}
            l0 = sess.run(m["loss"], feed)
            for _ in range(5):
                _, l = sess.run([m["train_op"], m["loss"]], feed)
            assert np.isfinite(l) and l < l0


def test_long_context_single_device_fallback():
    from simple_tensorflow_tpu.models import long_context as lc

    cfg = lc.LongContextConfig.tiny()
    m = lc.lm_train_model(batch_size=1, seq_len=16, cfg=cfg,
                          compute_dtype=stf.float32)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        ids, tg = lc.synthetic_lm_batch(1, 16, cfg.vocab_size)
        l = sess.run(m["loss"], {m["input_ids"]: ids, m["targets"]: tg})
        assert np.isfinite(l)


def test_transformer_bf16_train_step():
    """Backward-pass coverage for the mixed-precision embedding lookup and
    the bf16 tied-logits head (regression: custom_vjp residuals held
    non-JAX types and crashed gradient tracing).

    Deflaked (ISSUE 4 satellite): the default noam schedule
    (warmup_steps=4000) leaves the first few steps with a learning rate
    below bf16 update resolution, so 4 steps sometimes wobbled UP.
    Pinning the seed and shortening warmup makes the 8-step decrease
    large (>1.0 nats across seeds, measured) and deterministic."""
    from simple_tensorflow_tpu.models import transformer as tr

    stf.reset_default_graph()
    stf.set_random_seed(0)
    cfg = tr.TransformerConfig.tiny()
    m = tr.transformer_train_model(batch_size=2, src_len=8, tgt_len=8,
                                   cfg=cfg, compute_dtype=stf.bfloat16,
                                   warmup_steps=8)
    batch = tr.synthetic_wmt_batch(2, 8, 8, vocab_size=cfg.vocab_size)
    feed = {m[k]: v for k, v in batch.items()}
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        l0 = sess.run(m["loss"], feed)
        for _ in range(8):
            sess.run(m["train_op"], feed)
        l1 = sess.run(m["loss"], feed)
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    assert l1 < l0 - 0.5, (l0, l1)


def test_bert_recompute_trains():
    """recompute=True (per-layer jax.checkpoint) trains end-to-end with
    the full pretraining config (dropout inside the checkpointed blocks —
    the RNG prefetch must keep fwd/remat streams identical). Exact
    gradient parity on SHARED weights is covered by
    test_framework_extras.TestRecomputeGrad; cross-graph loss equality is
    not testable (initializer seeds derive from op counters, which the
    extra remat call ops shift)."""
    from simple_tensorflow_tpu.models import bert

    stf.reset_default_graph()
    cfg = bert.BertConfig.tiny()
    cfg.attention_dropout = 0.1
    cfg.hidden_dropout = 0.1
    m = bert.bert_pretrain_model(batch_size=2, seq_len=16,
                                 max_predictions=4, cfg=cfg,
                                 compute_dtype=stf.float32,
                                 learning_rate=1e-3, use_input_mask=True,
                                 recompute=True)
    batch = bert.synthetic_pretrain_batch(2, 16, 4,
                                          vocab_size=cfg.vocab_size)
    batch["input_mask"] = np.ones((2, 16), np.int32)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        feed = {m[k]: v for k, v in batch.items()}
        l0 = float(np.asarray(sess.run(m["loss"], feed)))
        for _ in range(8):
            sess.run(m["train_op"], feed)
        l1 = float(np.asarray(sess.run(m["loss"], feed)))
    assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0, (l0, l1)


def test_transformer_recompute_trains():
    from simple_tensorflow_tpu.models import transformer as tr

    stf.reset_default_graph()
    cfg = tr.TransformerConfig.tiny()
    m = tr.transformer_train_model(batch_size=2, src_len=8, tgt_len=8,
                                   cfg=cfg, compute_dtype=stf.bfloat16,
                                   recompute=True)
    batch = tr.synthetic_wmt_batch(2, 8, 8, vocab_size=cfg.vocab_size)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        feed = {m[k]: v for k, v in batch.items() if k in m}
        l0 = sess.run(m["loss"], feed)
        for _ in range(8):
            sess.run(m["train_op"], feed)
        l1 = sess.run(m["loss"], feed)
    assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0, (l0, l1)


def test_long_context_recompute_on_sp_mesh():
    """Remat composes with ring attention: jax.checkpoint replays the
    shard_map/ppermute body in the backward on the sp mesh."""
    from simple_tensorflow_tpu import parallel
    from simple_tensorflow_tpu.models import long_context as lc

    stf.reset_default_graph()
    cfg = lc.LongContextConfig.tiny()
    mesh = parallel.Mesh({"sp": 8})
    with mesh:
        m = lc.lm_train_model(batch_size=2, seq_len=128, cfg=cfg,
                              compute_dtype=stf.bfloat16, recompute=True)
        ids, tg = lc.synthetic_lm_batch(2, 128, vocab_size=cfg.vocab_size)
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            feed = {m["input_ids"]: ids, m["targets"]: tg}
            l0 = sess.run(m["loss"], feed)
            for _ in range(3):
                sess.run(m["train_op"], feed)
            l1 = sess.run(m["loss"], feed)
    assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0, (l0, l1)


def test_resnet_recompute_matches_baseline_losses():
    """recompute=True (per-block remat) must change bytes, not math: with
    IDENTICAL weights loaded, the training-step losses match the
    non-remat graph."""
    from simple_tensorflow_tpu.models import resnet

    images, labels = resnet.synthetic_imagenet(4, 64)
    labels = labels % 10
    losses = {}
    saved_vars = None
    for rc in (False, True):
        stf.reset_default_graph()
        stf.set_random_seed(7)
        m = resnet.resnet50_train_model(batch_size=4, image_size=64,
                                        num_classes=10, dtype=stf.float32,
                                        learning_rate=1e-2, recompute=rc)
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            if saved_vars is None:
                saved_vars = {v.var_name: np.asarray(
                    sess.variable_value(v))
                    for v in stf.global_variables()}
            else:
                for v in stf.global_variables():
                    v.load(saved_vars[v.var_name], session=sess)
            _, l1 = sess.run([m["train_op"], m["loss"]],
                             feed_dict={m["images"]: images,
                                        m["labels"]: labels})
            l2 = sess.run(m["loss"], feed_dict={m["images"]: images,
                                                m["labels"]: labels})
        losses[rc] = (float(l1), float(l2))
        assert np.isfinite(l1) and np.isfinite(l2)
    np.testing.assert_allclose(losses[False], losses[True],
                               rtol=2e-4, atol=2e-4)


def test_ptb_lstm_trains_with_state_carry():
    """PTB LSTM LM (TF-1.0 tutorial family): stacked LSTM via one
    lax.scan, truncated BPTT carrying state across session.run calls,
    global-norm clipping, assignable lr."""
    from simple_tensorflow_tpu.models import ptb_lstm

    stf.reset_default_graph()
    stf.set_random_seed(3)
    cfg = ptb_lstm.PTBConfig.tiny()
    B = 8
    m = ptb_lstm.ptb_lm_model(B, cfg, training=True)
    x, y = ptb_lstm.synthetic_ptb_batch(B, cfg.seq_len, cfg.vocab_size)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        state = ptb_lstm.zero_state(B, cfg)
        feed0 = {m["input_ids"]: x, m["target_ids"]: y,
                 **ptb_lstm.state_feed(m, state)}
        l0 = sess.run(m["loss"], feed0)
        losses = []
        for step in range(120):
            feed = {m["input_ids"]: x, m["target_ids"]: y,
                    **ptb_lstm.state_feed(m, state)}
            fetched = sess.run(
                [m["train_op"], m["loss"]] + [t for st in m["state_out"]
                                              for t in (st.c, st.h)], feed)
            losses.append(fetched[1])
            flat = fetched[2:]
            state = [(flat[2 * i], flat[2 * i + 1])
                     for i in range(cfg.layers)]
        # state actually carries (non-zero after a step)
        assert np.abs(state[0][1]).max() > 0
        assert losses[-1] < l0 * 0.8, (l0, losses[-1])
        # lr assignment (epoch decay idiom)
        sess.run(m["lr_update"], {m["new_lr"]: 0.25})
        assert sess.run(m["lr"].value()) == 0.25


def test_ptb_lstm_eval_mode_no_dropout_deterministic():
    from simple_tensorflow_tpu.models import ptb_lstm

    stf.reset_default_graph()
    cfg = ptb_lstm.PTBConfig.tiny()
    m = ptb_lstm.ptb_lm_model(4, cfg, training=False)
    x, y = ptb_lstm.synthetic_ptb_batch(4, cfg.seq_len, cfg.vocab_size)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        state = ptb_lstm.zero_state(4, cfg)
        feed = {m["input_ids"]: x, m["target_ids"]: y,
                **ptb_lstm.state_feed(m, state)}
        a = sess.run(m["loss"], feed)
        b = sess.run(m["loss"], feed)
    assert a == b  # no dropout in eval: bit-deterministic


def test_conv0_space_to_depth_equivalence_and_training():
    """The S2D stem is an exact reformulation: an 8x8/s2 VALID conv on
    the image equals a 4x4/s1 VALID conv on space_to_depth(image, 2)
    with re-laid-out weights (channel order (dy*2+dx)*C + c). Also: the
    full model trains with conv0_space_to_depth=True."""
    rng = np.random.RandomState(0)
    img = rng.randn(2, 16, 16, 3).astype(np.float32)
    w8 = rng.randn(8, 8, 3, 5).astype(np.float32)
    # re-layout: w4[py, px, (dy*2+dx)*3 + c, o] = w8[2py+dy, 2px+dx, c, o]
    w4 = np.zeros((4, 4, 12, 5), np.float32)
    for py in range(4):
        for px in range(4):
            for dy in range(2):
                for dx in range(2):
                    w4[py, px, (dy * 2 + dx) * 3:(dy * 2 + dx) * 3 + 3] = \
                        w8[2 * py + dy, 2 * px + dx]
    stf.reset_default_graph()
    x = stf.constant(img)
    ref = stf.nn.conv2d(x, stf.constant(w8), [1, 2, 2, 1], "VALID")
    s2d = stf.space_to_depth(x, 2)
    alt = stf.nn.conv2d(s2d, stf.constant(w4), [1, 1, 1, 1], "VALID")
    with stf.Session() as sess:
        rv, av = sess.run([ref, alt])
    np.testing.assert_allclose(rv, av, rtol=1e-4, atol=1e-4)

    # model trains with the S2D stem
    from simple_tensorflow_tpu.models import resnet

    stf.reset_default_graph()
    m = resnet.resnet50_train_model(batch_size=4, image_size=64,
                                    num_classes=10, dtype=stf.float32,
                                    learning_rate=1e-2,
                                    conv0_space_to_depth=True)
    images, labels = resnet.synthetic_imagenet(4, 64)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        _, l1 = sess.run([m["train_op"], m["loss"]],
                         feed_dict={m["images"]: images,
                                    m["labels"]: labels % 10})
    assert np.isfinite(l1)


def test_dlrm_trains():
    from simple_tensorflow_tpu.models import dlrm

    m = dlrm.dlrm_model(batch_size=16, num_dense=4,
                        table_sizes=(200, 100), embedding_dim=8,
                        max_ids_per_feature=6, bottom_mlp=(16, 8),
                        top_mlp=(16, 1), learning_rate=0.2)
    batch = dlrm.synthetic_dlrm_batch(16, num_dense=4,
                                      table_sizes=(200, 100),
                                      max_ids_per_feature=6, seed=3)
    feed = dlrm.feed_dict_for(m, batch)
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        l0 = float(np.asarray(sess.run(m["loss"], feed)))
        for _ in range(30):
            sess.run(m["train_op"], feed)
        l1 = float(np.asarray(sess.run(m["loss"], feed)))
    assert np.isfinite(l1) and l1 < l0 * 0.9, (l0, l1)
    # prediction head stays a probability
    with stf.Session() as sess:
        sess.run(stf.global_variables_initializer())
        p = sess.run(m["prediction"], feed)
    assert p.shape == (16, 1) and (p >= 0).all() and (p <= 1).all()


def test_dlrm_trains_on_ep_mesh():
    """Same graph, ep=8 mesh: the fused vocab-sharded lookup path."""
    from simple_tensorflow_tpu import parallel
    from simple_tensorflow_tpu.models import dlrm

    with parallel.Mesh({"ep": 8}):
        m = dlrm.dlrm_model(batch_size=16, num_dense=4,
                            table_sizes=(512, 256), embedding_dim=8,
                            max_ids_per_feature=6, bottom_mlp=(16, 8),
                            top_mlp=(16, 1), learning_rate=0.2)
        batch = dlrm.synthetic_dlrm_batch(16, num_dense=4,
                                          table_sizes=(512, 256),
                                          max_ids_per_feature=6, seed=5)
        feed = dlrm.feed_dict_for(m, batch)
        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            l0 = float(np.asarray(sess.run(m["loss"], feed)))
            for _ in range(20):
                sess.run(m["train_op"], feed)
            l1 = float(np.asarray(sess.run(m["loss"], feed)))
        assert np.isfinite(l1) and l1 < l0, (l0, l1)
