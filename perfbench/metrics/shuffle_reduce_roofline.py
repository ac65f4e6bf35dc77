"""kernels.ops: share of the HBM roofline reached by the merge-and-reduce
program (``_merge_reduce``): the bytes its work must move, from the
refreshes' counts (perfbench.harness.costs), over its device time in the
trace, against the chip's peak HBM bandwidth."""
from perfbench.harness.costs import merge_reduce_bytes

PROGRAM = "_merge_reduce"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    device_s = sum(s for name, s in run.trace.modules.items()
                   if PROGRAM in name) / max(run.trace.devices, 1)
    moved = sum(merge_reduce_bytes(r.store_rows_appended, r.affected_keys,
                                   run.job.value_width)
                for r in run.window.refreshes)
    if device_s <= 0 or moved <= 0:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / device_s
