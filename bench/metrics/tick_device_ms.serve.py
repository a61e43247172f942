"""Device milliseconds per tick of the close, refresh and dynamics programs,
from the trace (ticks counted as executions of the dynamics program)."""
import trace_reduce


def read(layer):
    ticks, _ = trace_reduce.module_stats(layer.trace, layer.programs["dynamics"])
    _, seconds = trace_reduce.module_stats(layer.trace, layer.programs["tick"])
    if not ticks:
        return None
    return 1e3 * seconds / ticks
