"""Host cost of the program's span API, with and without a profiler
trace running.

    python3 benchmarks/tools/span_cost.py [--n 200000]

Times ``obs.span`` (nested one deep, as the worker's spans are) and
``obs.timer`` with a trace name, first with no trace active, then inside
an active ``jax.profiler`` trace (Python tracer off, as the benchmark
traces), and prints one JSON line of microseconds per call. It measures
the ``obs`` of the checkout it lies in.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _per_call_us(fn, n: int) -> float:
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn(n)
        best = min(best, time.perf_counter() - t0)
    return 1e6 * best / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args(argv)
    import jax

    from distributed_backtesting_exploration_tpu import obs

    hist = obs.get_registry().histogram("dbx_span_cost_probe_seconds")

    def spans(n):
        with obs.span("span_cost.outer"):
            for _ in range(n):
                with obs.span("span_cost.inner"):
                    pass

    def timers(n):
        for _ in range(n):
            with obs.timer(hist, name="span_cost.timer"):
                pass

    def bare(n):
        for _ in range(n):
            with obs.timer(hist):
                pass

    try:
        timers(1)
    except TypeError:    # an obs whose timer takes no trace name
        timers = None
    out = {"device": jax.devices()[0].device_kind,
           "span_us_off": _per_call_us(spans, args.n),
           "timer_us": _per_call_us(bare, args.n)}
    if timers is not None:
        out["named_timer_us_off"] = _per_call_us(timers, args.n)
    d = tempfile.mkdtemp(prefix="span-cost-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        # Fewer calls under the trace: every one is an event it keeps.
        n_on = max(args.n // 10, 1)
        out["span_us_on"] = _per_call_us(spans, n_on)
        if timers is not None:
            out["named_timer_us_on"] = _per_call_us(timers, n_on)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
