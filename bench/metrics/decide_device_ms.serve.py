"""Device milliseconds per execution of the decide program (one flush part),
from the trace."""
import trace_reduce


def read(layer):
    n, seconds = trace_reduce.module_stats(layer.trace, layer.programs["decide"])
    if not n:
        return None
    return 1e3 * seconds / n
