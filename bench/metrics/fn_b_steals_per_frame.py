"""Cross-chip steals of function B's workers per delivered frame: the
elements a chip's worker claimed beyond its segment's border, summed over
the window's sessions (``SeriesSession.traffic()["steals"]``, the
driver's ``fn_b_steals``), over the frames delivered.  None where the
program does not report it."""


def read(inp):
    frames = inp.counters.get("frames", 0)
    steals = inp.counters.get("fn_b_steals")
    return steals / frames if steals is not None and frames else None
