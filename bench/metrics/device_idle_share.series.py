"""Share of the traced window in which no operation ran on the device (%),
in the series cells: 1 - the union of device-op intervals / the window."""

import trace_reduce


def read(inp):
    return 100.0 * trace_reduce.summary(inp.trace)["idle_share"]
