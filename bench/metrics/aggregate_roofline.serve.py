"""Share of its roofline that the aggregate refresh reaches, in %: the least
time the chip could take for the refresh's work (``workcount.for_config``,
the larger of operations over the compute peak and bytes over HBM
bandwidth, ``peaks.least_time``) over its measured device time per
execution."""
import peaks
import trace_reduce
import workcount


def read(layer):
    n, seconds = trace_reduce.module_stats(layer.trace, layer.programs["refresh"])
    if not n or seconds <= 0.0:
        return None
    ops, nbytes = workcount.for_config(layer.config)
    least, _bound = peaks.least_time(ops, nbytes, layer.device_kind)
    return 100.0 * least / (seconds / n)
