"""chipbench: the readings a cell's limits are set from, on the chip.

    python3 chipbench/calibrate.py --workload <name> --seeds 11,12,... \
        [--control-seeds 3] [--seconds <s>]

One process, many seeds: set-up is paid once. For every seed it prints one
JSON line ``{"calibrate": {...}}`` with the numbers the comparison would
read from the PROGRAM (the lower reading is the largest over a dozen
seeds), and for the first ``--control-seeds`` seeds also from the CONTROL
(the reference in the program's place, in the precision below the one the
configuration states) and from each planted fault the cell can have, and
under ``passes`` whether each would come out ``correct`` under the cell's
limits file as it stands. The benchmark's own runs never call this;
PERF.md holds what it read.
Like run.py it refuses to start without the chip.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="serving: length of each seed's short window")
    args = ap.parse_args(argv)
    manifest = harness.load_manifest()
    cell, entry = harness.find_cell(manifest, args.workload)
    config = harness.load_json(harness.ROOT, entry["file"])
    mix = harness.load_json(harness.HERE, "traffic", cell["traffic"] + ".json")
    devices, peak = harness.require_device(cell["chips"])
    harness.enable_compile_cache()
    limits = harness.load_json(harness.HERE, "limits", cell["name"] + ".json")
    ctx = {"cell": cell, "config": config, "traffic": mix, "devices": devices,
           "limits": limits,
           "peak": peak, "model_name": config.get("model_name",
                                                  config["name"])}
    runner = importlib.import_module("chipbench.runners." + config["runner"])
    t0 = time.perf_counter()
    runner.calibrate(ctx, [int(s) for s in args.seeds.split(",")],
                     args.control_seeds, args.seconds)
    harness.log(calibrate_total_s=time.perf_counter() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
