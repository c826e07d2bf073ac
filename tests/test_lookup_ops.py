"""Lookup tables (ref: core/kernels/lookup_table_op.cc,
contrib/lookup/lookup_ops.py). Covers the host string path, the
frozen-dense device fast path, mutability, OOV buckets, and the
end-to-end text pipeline the reference supports (vocab file -> ids ->
training -> decoded strings)."""

import numpy as np
import pytest

import simple_tensorflow_tpu as stf


def _write_vocab(tmp_path, tokens, name="vocab.txt"):
    p = tmp_path / name
    p.write_text("\n".join(tokens) + "\n")
    return str(p)


class TestHashTable:
    def test_string_to_int_lookup_with_default(self):
        stf.reset_default_graph()
        table = stf.lookup.HashTable(
            stf.lookup.KeyValueTensorInitializer(
                np.array(["a", "b", "c"], dtype=object),
                np.array([0, 1, 2], dtype=np.int64)),
            default_value=-1)
        keys = stf.constant(np.array(["b", "zzz", "a"], dtype=object))
        out = table.lookup(keys)
        size = table.size()
        with stf.Session() as sess:
            sess.run(stf.tables_initializer())
            ov, sv = sess.run([out, size])
        np.testing.assert_array_equal(ov, [1, -1, 0])
        assert sv == 3

    def test_lookup_before_init_raises(self):
        stf.reset_default_graph()
        table = stf.lookup.HashTable(
            stf.lookup.KeyValueTensorInitializer(
                np.array(["a"], dtype=object),
                np.array([7], dtype=np.int64)),
            default_value=-1)
        out = table.lookup(stf.constant(np.array(["a"], dtype=object)))
        with stf.Session() as sess:
            with pytest.raises(stf.errors.FailedPreconditionError,
                               match="not initialized"):
                sess.run(out)

    def test_double_init_is_noop(self):
        stf.reset_default_graph()
        table = stf.lookup.HashTable(
            stf.lookup.KeyValueTensorInitializer(
                np.array(["x"], dtype=object),
                np.array([5], dtype=np.int64)),
            default_value=-1)
        with stf.Session() as sess:
            sess.run(stf.tables_initializer())
            sess.run(stf.tables_initializer())
            assert sess.run(table.size()) == 1

    def test_int_keys_device_fast_path(self):
        # int64 -> float table lowers to a DEVICE op (searchsorted+gather
        # embedded in the XLA program), composable with device math.
        stf.reset_default_graph()
        table = stf.lookup.HashTable(
            stf.lookup.KeyValueTensorInitializer(
                np.array([10, 20, 30], dtype=np.int64),
                np.array([1.5, 2.5, 3.5], dtype=np.float32)),
            default_value=0.0)
        keys = stf.constant(np.array([30, 99, 10], dtype=np.int64))
        looked = table.lookup(keys)
        assert looked.op.type == "LookupTableFindDevice"
        out = looked * 2.0  # composes with device ops, no host hop
        with stf.Session() as sess:
            sess.run(stf.tables_initializer())
            np.testing.assert_allclose(sess.run(out), [7.0, 0.0, 3.0])

    def test_id_to_string_decoding(self):
        stf.reset_default_graph()
        table = stf.lookup.index_to_string_table_from_tensor(
            ["hello", "world"], default_value="UNK")
        out = table.lookup(stf.constant(np.array([1, 0, 9], dtype=np.int64)))
        with stf.Session() as sess:
            sess.run(stf.tables_initializer())
            ov = sess.run(out)
        assert list(ov) == ["world", "hello", "UNK"]


class TestTextFileInitializer:
    def test_index_table_from_file(self, tmp_path):
        stf.reset_default_graph()
        vocab = _write_vocab(tmp_path, ["the", "quick", "brown", "fox"])
        table = stf.lookup.index_table_from_file(vocab)
        out = table.lookup(stf.constant(
            np.array(["fox", "the", "missing"], dtype=object)))
        with stf.Session() as sess:
            sess.run(stf.tables_initializer())
            np.testing.assert_array_equal(sess.run(out), [3, 0, -1])

    def test_vocab_size_truncation_and_validation(self, tmp_path):
        stf.reset_default_graph()
        vocab = _write_vocab(tmp_path, ["a", "b", "c"])
        table = stf.lookup.index_table_from_file(vocab, vocab_size=2)
        out = table.lookup(stf.constant(np.array(["c"], dtype=object)))
        with stf.Session() as sess:
            sess.run(stf.tables_initializer())
            assert sess.run(out)[0] == -1  # truncated out of vocab
        stf.reset_default_graph()
        bad = stf.lookup.index_table_from_file(vocab, vocab_size=5)
        o2 = bad.lookup(stf.constant(np.array(["a"], dtype=object)))
        with stf.Session() as sess:
            with pytest.raises(stf.errors.InvalidArgumentError,
                               match="vocab_size"):
                sess.run([stf.tables_initializer(), o2])

    def test_oov_buckets_deterministic_and_in_range(self, tmp_path):
        stf.reset_default_graph()
        vocab = _write_vocab(tmp_path, ["a", "b"])
        table = stf.lookup.index_table_from_file(vocab, num_oov_buckets=4)
        keys = stf.constant(
            np.array(["a", "wat", "b", "wat"], dtype=object))
        out = table.lookup(keys)
        with stf.Session() as sess:
            sess.run(stf.tables_initializer())
            ov = sess.run(out)
        assert ov[0] == 0 and ov[2] == 1
        assert 2 <= ov[1] < 6 and ov[1] == ov[3]

    def test_text_file_initializer_columns(self, tmp_path):
        stf.reset_default_graph()
        p = tmp_path / "kv.txt"
        p.write_text("apple\t42\nbanana\t7\n")
        table = stf.lookup.HashTable(
            stf.lookup.TextFileInitializer(
                str(p), stf.string, 0, stf.int64, 1), default_value=-1)
        out = table.lookup(stf.constant(
            np.array(["banana", "apple"], dtype=object)))
        with stf.Session() as sess:
            sess.run(stf.tables_initializer())
            np.testing.assert_array_equal(sess.run(out), [7, 42])


class TestMutableHashTable:
    def test_insert_find_export(self):
        stf.reset_default_graph()
        table = stf.lookup.MutableHashTable(stf.string, stf.int64,
                                            default_value=-1)
        ins = table.insert(
            stf.constant(np.array(["k1", "k2"], dtype=object)),
            stf.constant(np.array([10, 20], dtype=np.int64)))
        out = table.lookup(stf.constant(
            np.array(["k2", "nope"], dtype=object)))
        ek, ev = table.export()
        with stf.Session() as sess:
            sess.run(ins)
            np.testing.assert_array_equal(sess.run(out), [20, -1])
            kv, vv = sess.run([ek, ev])
            assert sorted(kv.tolist()) == ["k1", "k2"]
            assert sess.run(table.size()) == 2

    def test_mutable_dense_alias(self):
        stf.reset_default_graph()
        table = stf.lookup.MutableDenseHashTable(
            stf.int64, stf.float32, default_value=0.0, empty_key=-1)
        ins = table.insert(stf.constant(np.array([3], dtype=np.int64)),
                           stf.constant(np.array([1.25], dtype=np.float32)))
        out = table.lookup(stf.constant(np.array([3, 4], dtype=np.int64)))
        with stf.Session() as sess:
            sess.run(ins)
            np.testing.assert_allclose(sess.run(out), [1.25, 0.0])


class TestEndToEndTextPipeline:
    def test_vocab_to_ids_to_training_to_decoded_strings(self, tmp_path):
        """The full journey: vocab file -> string
        tokens -> ids -> embedding training step -> predicted ids ->
        decoded strings, all through stf API."""
        stf.reset_default_graph()
        tokens = ["<pad>", "cat", "dog", "bird", "fish"]
        vocab = _write_vocab(tmp_path, tokens)

        to_id = stf.lookup.index_table_from_file(vocab)
        to_str = stf.lookup.index_to_string_table_from_file(vocab)

        words = stf.constant(
            np.array(["cat", "dog", "fish", "bird"], dtype=object))
        ids = to_id.lookup(words)  # host stage -> boundary feed

        emb = stf.get_variable(
            "emb", shape=(5, 8),
            initializer=stf.random_normal_initializer(seed=1))
        vecs = stf.nn.embedding_lookup(emb, stf.cast(ids, stf.int32))
        logits = stf.layers.dense(vecs, 5, name="out")
        labels = stf.cast(ids, stf.int32)  # autoencoder-style target
        loss = stf.reduce_mean(
            stf.nn.sparse_softmax_cross_entropy_with_logits(
                labels=labels, logits=logits))
        opt = stf.train.GradientDescentOptimizer(0.5)
        train_op = opt.minimize(loss)

        pred_ids = stf.cast(stf.argmax(logits, axis=-1), stf.int64)
        decoded = to_str.lookup(pred_ids)

        with stf.Session() as sess:
            sess.run(stf.global_variables_initializer())
            sess.run(stf.tables_initializer())
            l0 = sess.run(loss)
            for _ in range(60):
                sess.run(train_op)
            l1, dec = sess.run([loss, decoded])
        assert l1 < l0 * 0.5
        assert list(dec) == ["cat", "dog", "fish", "bird"]
