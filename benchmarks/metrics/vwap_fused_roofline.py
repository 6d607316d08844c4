"""Share of its roofline reached by the VWAP fused sweep: the least time
the chip needs for the work of the jobs completed in the window (the
reference's operation and byte count, against the chip's published
peaks), over the device time of every program of the fused VWAP call in
the window (its compensated z-table prep and the Pallas kernel), in
percent."""

from harness import peaks

MODULES = r"fused_vwap_call"


def read(run):
    if run.trace is None or run.config["strategy"] != "vwap_reversion":
        return None
    ops, nbytes = run.work()
    return peaks.roofline_share(ops, nbytes,
                                run.trace.module_seconds(MODULES),
                                run.device_kind)
