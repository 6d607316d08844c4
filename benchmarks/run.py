"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic and metrics are named in
``BENCHMARK.json``. The run holds the chip itself (one process), builds
its data from ``--seed``, warms up the cell's own shapes, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line last on standard output. Without a
TPU, or with another number of chips than the cell asks for, it exits
non-zero and prints no result.
"""

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (HERE, os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import cell as cell_mod  # noqa: E402
from harness import runner  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(levelname)s "
                               "%(message)s")
    cell, config, traffic = cell_mod.workload(args.workload)
    try:
        line = runner.execute(cell, config, traffic, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t_process=T_PROCESS)
    except runner.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
