"""Share of the fused VWAP call's device time spent outside its Pallas
kernel (the op named ``vwap_kernel``), in percent: the z-table prep
(window sums, deviation, moments) against the band machine, so it shows
which of the two sets the pace."""

import re

MODULES = re.compile(r"fused_vwap_call")
KERNEL = re.compile(r"/vwap_kernel(\.\d+)?$")


def read(run):
    if run.trace is None:
        return None
    total = run.trace.module_seconds(MODULES.pattern)
    kernel = sum(v for k, v in run.trace.op_s.items()
                 if MODULES.search(k.split("/", 1)[0]) and KERNEL.search(k))
    if total <= 0 or kernel <= 0:
        return None
    return 100.0 * (total - kernel) / total
