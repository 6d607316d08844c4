"""Run one cell as ``run.py`` does, and also print the window's sums of
every program span and RPC histogram.

    python3 benchmarks/tools/phase_sums.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--out <file.json>]

The result line goes to standard output as ``run.py`` prints it. The
sums (seconds and counts over the measured window, per
``dbx_span_seconds{span=...}``, ``dbx_rpc_seconds{method=...}`` and
``dbx_worker_rpc_seconds{method=...}``) go to ``--out`` as one JSON
object: what shows whether the collect lane's spans (device wait, copy
back, packing) tile ``worker.collect``. A ``--workload`` that is not a
cell of ``BENCHMARK.json`` is read as ``<config>.<traffic>`` on one chip,
so a held-out traffic mix (``sp500_daily_sma.served_small``) runs too.
"""

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for _p in (BENCH, os.path.dirname(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import cell as cell_mod  # noqa: E402
from harness import runner, serve  # noqa: E402

_FAMILIES = ("dbx_span_seconds{", "dbx_rpc_seconds{",
             "dbx_worker_rpc_seconds{")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seen = []
    deltas = serve.counter_deltas

    def keep(after, before):
        out = deltas(after, before)
        seen.append(out)
        return out
    serve.counter_deltas = keep
    cells = {w["name"] for w in cell_mod.benchmark()["workloads"]}
    if args.workload in cells:
        cell, config, traffic = cell_mod.workload(args.workload)
    else:
        name, mix = args.workload.rsplit(".", 1)
        cell, config, traffic = cell_mod.files(
            {"name": args.workload, "config": name, "traffic": mix,
             "chips": 1})
    line = runner.execute(cell, config, traffic, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_process=T_PROCESS)
    sums = {k: v for k, v in (seen[-1] if seen else {}).items()
            if k.startswith(_FAMILIES)}
    doc = {"workload": args.workload, "seed": args.seed,
           "trace": args.trace, "sums": sums}
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
