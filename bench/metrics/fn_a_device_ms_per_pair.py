"""Function A's device milliseconds per frame pair: the device time of the
batched pair programs (``jax.vmap`` of ``register_pair``, compiled by the
session as ``jit__lambda``) in the window, over the pairs fed in it.
None when the trace holds no such program."""

import trace_reduce

MODULE = "jit__lambda"


def read(inp):
    secs = sum(v for k, v in trace_reduce.seconds_by_name(
        inp.trace.modules[0], inp.trace.window).items()
        if k.startswith(MODULE))
    pairs = inp.counters.get("pairs", 0)
    return 1e3 * secs / pairs if secs > 0 and pairs else None
