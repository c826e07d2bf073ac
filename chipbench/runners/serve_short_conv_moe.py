"""Runner ``serve_short_conv_moe``: ``runners/serve_with_reference.py``'s
drive of ``ModelServer.generate`` for a configuration whose reference is
``chipbench/reference/short_conv_moe_decoder.py``.

This file's own: weights (made on the device a leaf at a time, as the
reference names them) and the traced stretch's work
(``work_short_conv_moe.py``): the model's FLOPs for ``mfu.serve`` and the
short-convolution update kernel's FLOPs and bytes for
``short_conv_roofline``.
"""

from __future__ import annotations

import sys

from chipbench import work_short_conv_moe
from chipbench.reference import short_conv_moe_decoder as ref
from chipbench.runners import serve_with_reference as drive_ref
from chipbench.runners.serve_sparse_moe import _prefill_spans, _share_inside


def load_weights(model, config, seed):
    """The seed's weights into the served model's variables, leaf by leaf
    as the reference makes them, in the dtype the program stores; each
    variable lets go of its initial value before its leaf is made (the
    weights and the K/V pool leave room for one more leaf, not two copies
    of the largest)."""
    import numpy as np
    import simple_tensorflow_tpu as stf

    with model.graph.as_default():
        by_name = {v.name.split(":")[0]: v for v in stf.trainable_variables()}

    def put(template, make, **fmt):
        var = by_name.pop(template.format(**fmt))
        shape = tuple(var.shape.as_list())
        var.load(np.zeros((), var.dtype.base_dtype.np_dtype), model.session)
        value = make()
        if shape != value.shape:
            raise RuntimeError(f"{var.name}: program shape {var.shape} != "
                               f"reference shape {value.shape}")
        var.load(value, model.session)

    spec = config["reference"]["spec"]
    names = config["variables"]
    for leaf, make in ref.top_leaf_makers(spec, seed, stored=True):
        put(names[leaf], make)
    for i in range(spec["layers"]):
        for leaf, make in ref.layer_leaf_makers(spec, seed, i, stored=True):
            put(names["layers." + leaf], make, i=i)
    if by_name:
        raise RuntimeError("trainable variables the configuration's name "
                           f"map does not cover: {sorted(by_name)}")


def traced_work(spec, reqs, t0, t1):
    """What was processed inside the traced stretch [t0, t1], by
    ``work_short_conv_moe``. ``model_flops``: every token delivered in it
    (one decode position over its context) and of every prompt the share
    of its prefill that lay inside (``serve_sparse_moe._prefill_spans``).
    ``short_conv_update``: ``[flops, bytes]`` of the decode kernel's calls,
    all conv layers: a delivered token is one row of one decode call a
    layer, which read and wrote that row's window once."""
    work = work_short_conv_moe
    model_flops = prompts = 0.0
    decode_tokens = 0
    spans = _prefill_spans(reqs)
    for r in reqs:
        plen = len(r.prompt)
        for j, ts in enumerate(r.times):
            if t0 <= ts < t1:
                decode_tokens += 1
                model_flops += work.decode_flops(spec, plen + j)
        share = _share_inside(spans[r], t0, t1) if r in spans else 0.0
        prompts += share
        model_flops += share * work.prompt_flops(spec, plen - 1)
    rows = decode_tokens * work.count(spec, work.CONV)
    return {"model_flops": model_flops, "decode_tokens": decode_tokens,
            "prompts": prompts,
            "short_conv_update": [rows * work.short_conv_update_flops(spec),
                                  rows * work.short_conv_update_bytes(spec)]}


def check(config, seed, sample, eos_id, control=None, timings=None):
    return drive_ref.check(ref, config, seed, sample, eos_id, control,
                           timings)


def second_best_fault(config, seed, sample, rows, eos_id):
    return drive_ref.second_best_fault(sys.modules[__name__], config, seed,
                                       sample, rows, eos_id)


def run(ctx):
    return drive_ref.run(ctx, sys.modules[__name__])


def calibrate(ctx, seeds, n_control, seconds):
    drive_ref.calibrate(ctx, seeds, n_control, seconds,
                        sys.modules[__name__])
