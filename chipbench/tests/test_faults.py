"""`correct` has to come out false when the timed path is broken, and when
the control (the reference in a lower precision) stands in the program's
place. Each test skips the harness's look for a chip and drives the rest
of a run (run.measure) at tiny sizes on the CPU, with the cell's own
limits file."""

import numpy as np

from chipbench import compare
from chipbench.tests import tiny

SEED = 3_000_000_021


def _train_line(monkeypatch, broken_step=None):
    from chipbench.runners import train

    if broken_step is not None:
        monkeypatch.setattr(train, "_step", broken_step)
    config, job = tiny.bert()
    line, result = tiny.measure("bert-base.s512", config, job, SEED,
                                seconds=0.5)
    return line, result


def test_sound_training_run_is_correct(monkeypatch):
    line, result = _train_line(monkeypatch)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["info"]["window_compiles"]["compiles"] == 0


def test_state_returned_unchanged_is_not_correct(monkeypatch):
    def frozen(sess, fetches, feed):
        # the loss is computed, the update never lands
        return [None, sess.run(fetches[1], feed_dict=feed)]

    line, _ = _train_line(monkeypatch, frozen)
    assert line["correct"] is False
    over = {k for k, v in line["compared"].items()
            if v["value"] > v["limit"]}
    assert over, line["compared"]


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    def half(sess, fetches, feed):
        # the second half never reaches the step: the first half stands in
        # its place, so every mean is taken over the first half alone
        cut = {k: np.concatenate([v[:len(v) // 2]] * 2)
               for k, v in feed.items()}
        return sess.run(fetches, feed_dict=cut)

    line, _ = _train_line(monkeypatch, half)
    assert line["correct"] is False, line["compared"]


def test_training_control_is_not_correct():
    """The reference in float8, in the program's place, against the
    float32 reference: has to fail one of the cell's numbers. Float8's
    error grows with depth and width, so this runs deeper and wider than
    the other rehearsals (6 layers of 256; on the chip at the cell's own
    size the control reads 0.0018-0.0037 in ``grad1_diff`` on 12 of 12
    seeds, against the limit of 0.001)."""
    from chipbench import traffic
    from chipbench.reference import postln_transformer as ref

    config, job = tiny.bert()
    spec = config["reference"]["spec"]
    spec.update(hidden=256, ffn=1024, layers=6, heads=4, vocab=1000,
                max_position=64)
    job.update(batch=8, seq_len=64, masked_per_row=9)
    batches = traffic.train_batches(job, spec, SEED)[:job["check_steps"]]
    want = ref.bert_train_reference(spec, SEED, batches, job)
    control = ref.bert_train_reference(
        spec, SEED, batches, job, precision=config["control_precision"])
    numbers, _ = compare.train_numbers(control, want)
    compared = compare.against(numbers, tiny.load(
        "limits", "bert-base.s512.json"))
    assert compare.is_correct(compared) is False, compared
    # and the same reference in float32 passes, exactly
    same = compare.against(compare.train_numbers(want, want)[0],
                           tiny.load("limits", "bert-base.s512.json"))
    assert compare.is_correct(same) is True


def _serve_line(monkeypatch, on_token=None):
    from chipbench.runners import serve

    if on_token is not None:
        monkeypatch.setattr(serve._Request, "on_token", on_token)
    line, result = tiny.measure("lm-big.backlog", tiny.lm(),
                                tiny.mix("backlog"), SEED, seconds=1.5)
    return line, result


def test_sound_serving_run_is_correct(monkeypatch):
    line, result = _serve_line(monkeypatch)
    assert line["correct"] is True, line["compared"]
    assert result["info"]["checked"]["requests"] >= 2
    assert result["info"]["window_compiles"]["compiles"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    import time

    def altered(self, tok, _logp):
        self.times.append(time.perf_counter())
        n = len(self.tokens)
        self.tokens.append((int(tok) + 1) % 64 if n % 3 == 2 else int(tok))
        self.logprobs.append(float(_logp))

    line, result = _serve_line(monkeypatch, altered)
    assert line["correct"] is False, line["compared"]
    assert line["compared"]["logprob_gap"]["value"] > \
        line["compared"]["logprob_gap"]["limit"]
    assert result["info"]["checked"]["numbers"]["logit_gap"] > 0.0


def test_an_answer_cut_short_is_not_correct(monkeypatch):
    import time

    def lossy(self, tok, _logp):
        self.times.append(time.perf_counter())
        if len(self.tokens) < 2:          # every later token is dropped
            self.tokens.append(int(tok))
            self.logprobs.append(float(_logp))

    line, _ = _serve_line(monkeypatch, lossy)
    assert line["correct"] is False, line["compared"]
    assert line["compared"]["missing"]["value"] >= 1


def test_the_rate_credits_the_tokens_in_flight_at_the_close():
    import types

    from chipbench.runners import serve

    def answer(*times):
        return types.SimpleNamespace(times=list(times))

    # window [10, 20]: three delivered; the fourth's wait 19 -> 21 lies
    # half inside; a first token in flight, and a wait that began before
    # the window, get nothing
    reqs = [answer(12.0, 15.0, 19.0, 21.0), answer(20.5), answer()]
    assert serve.tokens_in_window(reqs, 10.0, 20.0) == 3.5
    # a delivery a millisecond either side of the close moves the count
    # by a thousandth of a token's wait, not by a token
    early = serve.tokens_in_window([answer(12.0, 19.999, 22.0)], 10.0, 20.0)
    late = serve.tokens_in_window([answer(12.0, 20.001, 22.0)], 10.0, 20.0)
    assert abs(early - late) < 0.002
