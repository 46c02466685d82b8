"""Function-B operator applications per delivered frame: the sessions'
``op_telemetry`` counts (``calls + compile_calls``, which include the one
cost prime each session records) over the frames delivered in the window.
A count: it measures the backend's work efficiency."""


def read(inp):
    frames = inp.counters.get("frames", 0)
    return inp.counters["fn_b_ops"] / frames if frames else None
