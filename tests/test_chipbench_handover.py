"""The benchmark's reader of the hand-over (``chipbench/readers/
idle_split_ms``: an idle gap cut at every boundary of the program's spans,
the executor's wait told apart as ``return`` or ``launch``), guarded by
tier-1: every case of ``chipbench/tests/test_handover.py`` run here by
import, as ``tests/test_chipbench_traffic.py`` does for the traffic — and
the manifest's side of it: the seven metrics this reader, the span reader
and the counter reader feed, each with its file, its layer and its cells."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench.tests import test_handover as _cases  # noqa: E402
from chipbench.tests.test_handover import *  # noqa: E402,F401,F403
from chipbench.tests.test_handover import (  # noqa: E402,F401
    facts, parent_facts)

# every case the module has is run here: a new one there is one more here
assert {n for n in dir(_cases) if n.startswith("test_")} <= set(globals())

SERVING = ["lm-big.backlog", "keye-vl2-30b-a3b.longdoc-backlog",
           "kimi-k2.7-code.repo-backlog",
           "nemotron-3-nano-30b-a3b.reasoning-backlog"]


@pytest.mark.parametrize("name, layer, source, reader, cells", [
    ("handover_return_ms.serve", "Session executor", "device_trace",
     "idle_split_ms", SERVING),
    ("handover_launch_ms.serve", "Session executor", "device_trace",
     "idle_split_ms", SERVING),
    ("host_serial_ms.serve", "serving scheduler", "device_trace",
     "idle_split_ms", SERVING),
    ("model_host_ms.serve", "model step", "program_span", "span_ms",
     SERVING),
    ("handover_return_ms.train", "Session executor", "device_trace",
     "idle_split_ms", ["bert-base.s512"]),
    ("handover_launch_ms.train", "Session executor", "device_trace",
     "idle_split_ms", ["bert-base.s512"]),
    ("await_device_ms.serve", "Session executor", "program_counter",
     "process_counter", SERVING),
])
def test_the_manifest_names_the_metric(name, layer, source, reader, cells):
    manifest = harness.load_manifest()
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "ms", "better": "lower", "source": source,
        "layer": layer, "workloads": cells,
        "moves": ("serve" if name.endswith(".serve") else "train")
        + "_tokens_per_s"}
    with open(os.path.join(harness.HERE, "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert (spec["layer"], spec["reader"]) == (layer, reader)
    for cell in cells:
        assert entry in harness.cell_metrics(manifest, "per_layer", cell)
    # appended: every metric the benchmark had stands before these seven
    assert manifest["per_layer"].index(entry) >= 29


def test_the_wait_and_its_sampler_bear_the_names_the_reader_looks_for():
    from simple_tensorflow_tpu.platform import monitoring
    import simple_tensorflow_tpu.client.session  # noqa: F401

    assert _cases.idle_split_ms.AWAIT == "stf/session/await_device"
    params = _cases.metric_params("await_device_ms.serve")
    sampler = monitoring.get_metric(params["metric"])
    assert isinstance(sampler, monitoring.Sampler)
    assert harness.read_counter(params["metric"], params["labels"])[
        "count"] >= 0
