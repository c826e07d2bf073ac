"""chipbench/work.py against numbers worked by hand."""

import json
import os

import pytest

from chipbench import work

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def spec_of(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["reference"]["spec"]


def test_bert_base_flops_per_token():
    spec = spec_of("bert-base")
    # one block: Q,K,V,O 4*768^2 = 2,359,296; FFN 2*768*3072 = 4,718,592
    block = 2 * (2_359_296 + 4_718_592) + 4 * 512 * 768      # 15,728,640
    assert work.transformer_forward_flops_per_token(768, 3072, 12, 512) \
        == 12 * block == 188_743_680
    # MLM head on 76 of 512 positions: transform 2*768^2, vocab 2*768*30522
    head = (76 / 512) * (1_179_648 + 46_881_792)             # 7,134,120
    rows = (1_179_648 + 3_072) / 512                         # 2,310
    want = 3 * (188_743_680 + head + rows)
    got = work.bert_train_flops_per_token(spec, 512, 76)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(0.5876e9, rel=1e-3)
    # the head on every position: the issue's "about 0.72 GFLOP a token"
    assert work.bert_train_flops_per_token(spec, 512, 512) == \
        pytest.approx(0.7104e9, rel=1e-3)


def test_lm_big_cache_bytes_and_decode():
    spec = spec_of("lm-big")
    assert work.kv_cache_bytes_per_token(spec) == 24_576
    # one decode position over 1000 keys: 6 layers of 2*(4*1024^2 +
    # 2*1024*4096) + 4*1000*1024, and the tied head 2*1024*32768
    layer = 2 * (4_194_304 + 8_388_608) + 4_096_000
    assert work.causal_lm_decode_flops(spec, 1000) == \
        6 * layer + 67_108_864
    # a prompt of 3 tokens attends over 1+2+3 keys
    assert work.causal_lm_prompt_flops(spec, 3) == \
        3 * 6 * 2 * 12_582_912 + 4 * 1024 * 6 * 6


def test_flash_attention_work_and_roofline():
    fwd = work.flash_attention_work(48, 12, 512, 512, 64, backward=False)
    mm = 2 * 48 * 12 * 512 * 512 * 64
    tensor = 48 * 12 * 512 * 64 * 2
    assert fwd == (2 * mm, 4 * tensor)
    assert work.flash_attention_work(48, 12, 512, 512, 64, backward=True) \
        == (5 * mm, 8 * tensor)
    peak = {"flops_per_s_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds(1000.0, 50.0, peak) == (10.0, "flops")
    assert work.roofline_seconds(100.0, 50.0, peak) == (5.0, "bytes")
