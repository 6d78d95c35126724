"""Readings the correctness limits are set from, on the chip, in one process.

    python bench/calibrate.py --workload protein-train --seeds 101-112 --stand-ins 3

For each seed: the cell's set-up (which drives the timed programs through
their first steps), then the gaps between what the program produced and
the plain reference: the lower readings. On the first ``--stand-ins``
seeds also the reference put in the program's place, as the control
(bfloat16 lattice tables) and with the fault ``half_batch`` planted in
it: the upper readings. One JSON line per seed, then the largest lower and
the smallest upper reading of each number beside the cell's limits
(``bench/limits/<workload>.json``). The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import enable_cache  # bench/ is on sys.path when run as a script

from bench import harness

STAND_INS = ("control", "half_batch")


def seeds_of(text: str) -> list[int]:
    if "-" in text:
        lo, hi = text.split("-")
        return list(range(int(lo), int(hi) + 1))
    return [int(s) for s in text.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--stand-ins", type=int, default=3)
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    import jax
    try:
        harness.check_devices(jax.devices(), cell["chips"],
                              harness.load_peaks())
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    enable_cache()
    config = harness.load_config(cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    drv_cls = harness.load_driver(traffic["driver"])
    lower, upper = {}, {kind: {} for kind in STAND_INS}
    for i, seed in enumerate(seeds_of(args.seeds)):
        t0 = time.perf_counter()
        drv = drv_cls(config, traffic, seed, harness.log)
        t1 = time.perf_counter()
        got = drv.check()
        t2 = time.perf_counter()
        row = {"seed": seed, "program": got, "setup_s": t1 - t0,
               "reference_s": t2 - t1}
        for k, v in got.items():
            lower[k] = max(lower.get(k, v), v)
        if i < args.stand_ins:
            for kind in STAND_INS:
                row[kind] = drv.stand_in(kind)
                for k, v in row[kind].items():
                    upper[kind][k] = min(upper[kind].get(k, v), v)
        print(json.dumps(row), flush=True)
        del drv
    print(json.dumps({"lower": lower, "upper": upper,
                      "limits": harness.load_limits(cell["name"])}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
