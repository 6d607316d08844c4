"""Share of its roofline reached by the pairs fused sweep: the least
time the chip needs for the work of the jobs completed in the window,
over the device time of every program of the fused pairs call in the
window (OLS prep and the Pallas kernel), in percent."""

from harness import peaks

MODULES = r"fused_pairs_call"


def read(run):
    if run.trace is None or run.config["strategy"] != "pairs":
        return None
    ops, nbytes = run.work()
    return peaks.roofline_share(ops, nbytes,
                                run.trace.module_seconds(MODULES),
                                run.device_kind)
