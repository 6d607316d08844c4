"""Share of its roofline reached by the SMA fused sweep: the least time
the chip needs for the work of the jobs completed in the window (the
reference's operation and byte count, against the chip's published
peaks), over the device time of every program of the fused sweep call
in the window (its prep fusions, the paged gather and the Pallas
kernel), in percent."""

from harness import peaks

MODULES = r"fused_call|paged_gather"


def read(run):
    if run.trace is None or run.config["strategy"] != "sma_crossover":
        return None
    ops, nbytes = run.work()
    return peaks.roofline_share(ops, nbytes,
                                run.trace.module_seconds(MODULES),
                                run.device_kind)
