"""Function B's device milliseconds per operator application: the device
time of the unbatched ``register_pair`` programs (``jit__register_pair``)
in the window, over the number of their executions.  None when the trace
holds none."""

import trace_reduce

MODULE = "jit__register_pair"


def read(inp):
    win = inp.trace.window
    secs = sum(v for k, v in trace_reduce.seconds_by_name(
        inp.trace.modules[0], win).items() if k.startswith(MODULE))
    calls = sum(v for k, v in trace_reduce.count_by_name(
        inp.trace.modules[0], win).items() if k.startswith(MODULE))
    return 1e3 * secs / calls if calls else None
