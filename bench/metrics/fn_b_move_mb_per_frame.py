"""Frame megabytes (10^6 bytes) function B copied between chips per
delivered frame: a stolen element's frames, and any frame an application
needed on a chip that did not hold it (``SeriesSession.traffic()
["moved_bytes"]``, the driver's ``fn_b_move_bytes``).  The placement
``feed`` makes (halo frames, frame 0 on every chip) is not in it.  None
where the program does not report it."""


def read(inp):
    frames = inp.counters.get("frames", 0)
    moved = inp.counters.get("fn_b_move_bytes")
    return moved / 1e6 / frames if moved is not None and frames else None
