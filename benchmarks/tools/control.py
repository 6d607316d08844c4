"""Read the control: the plain reference put in the program's place and
computed in bfloat16, the precision below the configuration's float32,
against the float64 reference, on the answers a run of the cell samples.

    python3 benchmarks/tools/control.py --workload <cell> --seeds 1,2,3 \
        [--dtype float32]

``--dtype float32`` reads the reference in plain float32 instead: its
window sums are uncompensated prefix-sum differences, which is how the
program computed them before it carried compensated sums, so it shows
whether the limit catches a return to that arithmetic.

Prints, per seed, the numbers the run's check compares (here only
``disagree_share``; nothing is missing or failed when nothing is
served) with the configuration's limit. The control has to read above
the limit on every seed; its smallest reading is the limit's upper
reading in PERF.md.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (HERE, os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from harness import cell as cell_mod  # noqa: E402
from harness import check, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dtype", choices=("bfloat16", "float32"),
                    default="bfloat16")
    args = ap.parse_args(argv)
    dt = ml_dtypes.bfloat16 if args.dtype == "bfloat16" else np.float32
    cell, config, tr = cell_mod.workload(args.workload)
    family = cell_mod.reference(config["strategy"])
    for seed in (int(s) for s in args.seeds.split(",")):
        wl = traffic.Workload(config, tr, seed)
        if wl.loop == "closed":
            insts = [int(i) for i in wl.sampled_instruments()]
        else:
            off, picks = wl.schedule(float(cell_mod.benchmark()
                                           ["run_seconds"]))
            n_lead = int(np.sum(off < 0))
            insts = [int(picks[k]) for k in
                     wl.sampled_jobs(off.size - n_lead, n_lead)]
        tally = check.Tally()
        cache = {}
        for i in insts:
            if i not in cache:
                legs = wl.legs(wl.instruments[i])
                kw = dict(cost=wl.cost, periods_per_year=wl.ppy)
                with np.errstate(all="ignore"):
                    cache[i] = (family.sweep(legs, wl.axes, **kw),
                                family.sweep(legs, wl.axes,
                                             dt=dt, **kw))
            tally.add(*cache[i])
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "dtype": args.dtype,
                          "disagree_share": tally.share,
                          "limit": config["correct"]["disagree_share"],
                          "cells": tally.cells}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
