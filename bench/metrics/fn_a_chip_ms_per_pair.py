"""Function A's device milliseconds per frame pair, summed over the
chips: the device time of the pair programs in the window on every device
plane (the sharded ``jit_fn_a_shards``, or ``jit__lambda`` where a
program vmaps the pairs on one device or in lock step across several),
over the pairs fed in it.  None when the trace holds no such program."""

import trace_reduce

MODULES = ("jit_fn_a_shards", "jit__lambda")


def read(inp):
    secs = sum(v for mods in inp.trace.modules
               for k, v in trace_reduce.seconds_by_name(
                   mods, inp.trace.window).items()
               if k.startswith(MODULES))
    pairs = inp.counters.get("pairs", 0)
    return 1e3 * secs / pairs if secs > 0 and pairs else None
