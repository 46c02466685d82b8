"""The busiest chip's device time over the mean across the cell's chips
(x): the union of device-op intervals in the window on each device plane.
1 when every chip is as busy as the busiest.  None when no chip ran."""

import trace_reduce


def read(inp):
    busy = [trace_reduce.busy_ns(ops, inp.trace.window)
            for ops in inp.trace.ops]
    mean = sum(busy) / len(busy)
    return max(busy) / mean if mean > 0 else None
