"""Function B's device milliseconds per application, summed over the
chips: the device time of the ``register_pair`` programs
(``jit__register_pair``) in the window on every device plane, over their
executions on all planes.  An application runs on one chip, so an
execution is an application; a program replicated over several chips
counts once on each.  None when the trace holds none."""

import trace_reduce

MODULE = "jit__register_pair"


def read(inp):
    win = inp.trace.window
    secs = calls = 0
    for mods in inp.trace.modules:
        secs += sum(v for k, v in trace_reduce.seconds_by_name(
            mods, win).items() if k.startswith(MODULE))
        calls += sum(v for k, v in trace_reduce.count_by_name(
            mods, win).items() if k.startswith(MODULE))
    return 1e3 * secs / calls if calls else None
