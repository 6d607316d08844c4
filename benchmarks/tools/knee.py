"""Find an open-loop cell's knee: the highest offered rate the served
path sustains, by a sweep of fixed rates in one process on the chip.

    python3 benchmarks/tools/knee.py --workload <cell> --seed <n> \
        --seconds 8 --rates 100,200,400,800

The cell's traffic file fixes the rate its runs offer; this tool is how
that rate was chosen (about four fifths of the knee). Per rate it
prints one JSON line: offered and completed jobs per second, the
latency median and 95th percentile (from the due time), the 95th
percentile of the window's first and last thirds, and how late the
generator ran. A rate is sustained when no job is missing, the
completions keep pace (98 % of the offered rate), the last third's tail
is no worse than twice the first third's, and the tail is no worse than
twice the lowest tail of the sweep. The last line names the knee: the highest
sustained rate.
"""

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (HERE, os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from harness import cell as cell_mod  # noqa: E402
from harness import runner, serve, traffic  # noqa: E402


def _p95(x):
    x = np.sort(np.asarray(x))
    return float(x[max(math.ceil(0.95 * x.size) - 1, 0)]) if x.size else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell, config, tr = cell_mod.workload(args.workload)
    runner._devices(cell, True)
    from distributed_backtesting_exploration_tpu.tune import compile_cache

    compile_cache.configure(min_compile_time_s=0.0)
    svc = serve.Service(config, tr["loop"])
    rows = []
    try:
        wl = traffic.Workload(config, tr, args.seed)
        runner._warm_open(svc, wl)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            wl.traffic = {**tr, "rate_per_s": rate, "lead_in_s": 1.0}
            off, picks = wl.schedule(args.seconds)
            recs = [wl.record(f"r{k}.{i}", wl.instruments[int(p)])
                    for i, p in enumerate(picks)]
            t0 = time.time() + 1.25
            due = t0 + off
            arr = serve.Arrivals(svc, due, recs)
            arr.start()
            svc.sleep_until(t0 + args.seconds)
            ids = [r.id for r in recs]
            svc.wait(ids, 30)
            arr.stop()
            win = off >= 0
            lat = np.asarray([svc.done.get(r.id, math.inf) - d
                              for r, d in zip(recs, due)])[win]
            done_t = np.asarray([svc.done[r.id] for r, w in zip(recs, win)
                                 if w and r.id in svc.done])
            thirds = np.array_split(lat, 3)
            row = {
                "rate_per_s": rate,
                "completed_per_s": float(np.sum(done_t <= t0 + args.seconds)
                                         / args.seconds),
                "missing": int(np.sum(~np.isfinite(lat))),
                "p50_s": float(np.median(lat)), "p95_s": _p95(lat),
                "p95_first_third_s": _p95(thirds[0]),
                "p95_last_third_s": _p95(thirds[-1]),
                "late_max_s": float(arr.lateness.max())}
            print(json.dumps(row), flush=True)
            rows.append(row)
            time.sleep(1.0)
    finally:
        svc.close()
    base = min(r["p95_s"] for r in rows)
    ok = [r["rate_per_s"] for r in rows
          if r["missing"] == 0
          and r["completed_per_s"] >= 0.98 * r["rate_per_s"]
          and r["p95_last_third_s"] <= 2 * r["p95_first_third_s"]
          and r["p95_s"] <= 2 * base]
    print(json.dumps({"knee_per_s": max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
