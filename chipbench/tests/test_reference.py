"""The plain reference against the program at tiny sizes on the CPU, and
the generators' determinism. The reference imports nothing of the program;
here the two are put side by side on the same seeded weights."""

import numpy as np
import pytest

from chipbench import compare, harness, traffic
from chipbench.reference import postln_transformer as ref
from chipbench.tests import tiny


def test_reference_agrees_with_bert_pretrain_model_in_float32():
    import simple_tensorflow_tpu as stf
    from chipbench.runners import train

    config, job = tiny.bert("float32")
    spec = config["reference"]["spec"]
    seed = 3_000_000_017
    model = train.build(config, job)
    batches = traffic.train_batches(job, spec, seed)[:job["check_steps"]]
    feeds = [{model[k]: v for k, v in b.items()} for b in batches]
    with stf.Session() as sess:
        variables = train.program_variables(config)
        init = train.load_weights(sess, variables,
                                  ref.init_params(spec, seed))
        got = train.first_steps(sess, model, variables, feeds, job, init)
    want = ref.bert_train_reference(spec, seed, batches, job)
    numbers, leaves = compare.train_numbers(got, want)
    # loss and gradients of the first step, then three Adam steps
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["grad1_gap"] < 2e-3, (numbers, leaves)
    assert numbers["change_gap"] < 2e-2, (numbers, leaves)
    assert got["losses"][0] == pytest.approx(want["losses"][0], rel=1e-5)
    # a key's bias has no gradient under softmax: left out by the rule
    assert all(name.endswith(".bk") or name.endswith(".bq")
               for name in leaves["leaves_left_out"])


def test_reference_agrees_with_causal_lm_logits_in_float32():
    import simple_tensorflow_tpu as stf
    from simple_tensorflow_tpu.models import causal_lm

    config = tiny.lm("float32")
    spec, prog = config["reference"]["spec"], config["program"]
    cfg = harness.import_attr(prog["config_class"])(**prog["config_kwargs"])
    seed = 2_147_483_900
    params = ref.init_params(spec, seed)
    ids = np.random.default_rng(3).integers(2, 64, size=(3, 24)).astype(
        np.int32)
    stf.reset_default_graph()
    ph = stf.placeholder(stf.int32, [3, 24], "ids")
    logits = causal_lm.causal_lm_logits(ph, cfg, training=False,
                                        compute_dtype=stf.float32)
    with stf.Session() as sess:
        variables = harness.map_variables(config, stf.trainable_variables())
        harness.load_variables(sess, variables, params)
        got = np.asarray(sess.run(logits, feed_dict={ph: ids}))
    want = np.asarray(ref.causal_lm_logits(params, ids, spec))
    assert got.shape == want.shape == (3, 24, 64)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_served_token_gaps_are_zero_for_the_references_own_greedy_tokens():
    config = tiny.lm("float32")
    spec = config["reference"]["spec"]
    params = ref.init_params(spec, 7)
    prompt = np.arange(2, 14, dtype=np.int32)
    toks = []
    for _ in range(5):
        ids = np.concatenate([prompt, np.asarray(toks, np.int32)])[None]
        toks.append(int(np.argmax(np.asarray(
            ref.causal_lm_logits(params, ids, spec))[0, -1])))
    rows = ref.served_token_gaps(spec, params, [prompt], [toks], pad_to=32,
                                 control="fp8")
    assert len(rows) == 1 and rows[0]["gap"].shape == (5,)
    assert float(rows[0]["gap"].max()) == 0.0
    assert (rows[0]["margin"] > 0).all() and (rows[0]["logprob"] < 0).all()
    assert rows[0]["control_gap"].shape == (5,)
    wrong = list(toks)
    wrong[2] = (wrong[2] + 1) % 64
    bad = ref.served_token_gaps(spec, params, [prompt], [wrong], pad_to=32)
    assert bad[0]["gap"][2] > 0.0 and float(bad[0]["gap"][:2].max()) == 0.0


def test_request_traffic_is_a_function_of_the_seed():
    mix = tiny.load("traffic", "backlog.json")
    a = traffic.requests(mix, 32768, 2_147_483_999, 5.0)
    b = traffic.requests(mix, 32768, 2_147_483_999, 5.0)
    c = traffic.requests(mix, 32768, 12, 5.0)
    assert len(a) == len(b) == len(c) > 0
    for x, y in zip(a, b):
        assert x["due"] == y["due"]
        assert x["max_new_tokens"] == y["max_new_tokens"]
        np.testing.assert_array_equal(x["prompt"], y["prompt"])
    # another seed: the same sizes in another order, other ids
    sizes = lambda rs: sorted((len(r["prompt"]), r["max_new_tokens"])
                              for r in rs)
    assert sizes(a) == sizes(c)
    assert ([len(r["prompt"]) for r in a]
            != [len(r["prompt"]) for r in c])
    # ... but for the queue's head, which an idle engine admits alone
    assert len(a[0]["prompt"]) == len(c[0]["prompt"])
    assert a[0]["max_new_tokens"] == c[0]["max_new_tokens"]
    # every block holds the same pairs of sizes
    block = mix["block"]
    assert sizes(a[:block]) == sizes(a[block:2 * block]) == sizes(c[:block])
    assert not np.array_equal(a[0]["prompt"][:8], c[0]["prompt"][:8])
    assert min(int(r["prompt"].min()) for r in a) >= 2     # no pad, no EOS
    assert all(a[i]["due"] <= a[i + 1]["due"] for i in range(len(a) - 1))
    assert all(mix["prompt_len"]["min"] <= len(r["prompt"])
               <= mix["prompt_len"]["max"] for r in a)


def test_training_batches_are_a_function_of_the_seed():
    config, job = tiny.bert()
    spec = config["reference"]["spec"]
    a = traffic.train_batches(job, spec, 2_147_483_999)
    b = traffic.train_batches(job, spec, 2_147_483_999)
    c = traffic.train_batches(job, spec, 5)
    assert len(a) == job["pool_batches"]
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    assert not np.array_equal(a[0]["input_ids"], c[0]["input_ids"])
    first = a[0]
    assert len({row.tobytes() for row in first["input_ids"]}) == job["batch"]
    real = first["input_mask"].sum(1)
    # the same lengths in every batch of every seed, in another order:
    # one short row of four (short_seq_prob 0.1, at least one), the rest full
    assert sorted(real) == sorted(traffic.row_lengths(job))
    assert sorted(c[1]["input_mask"].sum(1)) == sorted(real)
    assert (real == job["seq_len"]).sum() == job["batch"] - 1
    assert (first["mlm_positions"] < real[:, None]).all()
    # a row predicts 15% of its real tokens, at most masked_per_row; the
    # rest of its predictions carry weight 0, position 0 and id 0
    n_pred = first["mlm_weights"].sum(1)
    assert (n_pred == np.clip(np.round(real * job["masked_lm_prob"]), 1,
                              job["masked_per_row"])).all()
    assert n_pred.min() < job["masked_per_row"] == n_pred.max()
    padded = first["mlm_weights"] == 0
    assert (first["mlm_positions"][padded] == 0).all()
    assert (first["mlm_ids"][padded] == 0).all()
