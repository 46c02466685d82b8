"""The benchmark's one command: run one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name.  A cell of the manifest names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``, which names its driver,
``bench/drivers/<driver>.py``); its output limits are
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell or a metric adds files and
a manifest entry, and edits none.

A run: refuse any device but a TPU (exit 3, no result); set up (make the
inputs on the device from ``--seed``, warm every shape the window uses;
``setup_s`` runs from process start to the window's start); measure for
``--seconds`` (with ``--trace 1`` under the profiler, and the per-layer
metrics instead of the end-to-end ones); read the device's peak memory;
free the program's state; check every sampled answer against the plain
reference.  The compared numbers are the last lines of standard error,
each beside its limit, and the last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` a ``breakdown``, and last the ``checks``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # noqa: E402  (set-up starts here)

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
EXIT_NO_CHIP = 3
# The benchmark's packages (gen, reference) and the system under test.
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ loading by name


def load_module(path: str):
    """Import a file of the benchmark by path (its name may hold dots),
    once per process."""
    rel = os.path.relpath(path, ROOT).replace(os.sep, "_").replace(".", "_")
    name = f"_bench_{rel}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(*parts: str) -> Dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def driver(self):
        return load_module(os.path.join(BENCH, "drivers",
                                        self.traffic["driver"] + ".py"))


def reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(manifest: Dict, name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic and limits."""
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=read_json("configs", entry["config"] + ".json"),
        traffic=read_json("traffic", entry["traffic"] + ".json"),
        limits=read_json("limits", name + ".json"),
        end_to_end=[m for m in manifest["end_to_end"] if reports(m, name)],
        per_layer=[m for m in manifest["per_layer"] if reports(m, name)],
    )


def metric_reader(name: str) -> Callable:
    return load_module(os.path.join(BENCH, "metrics", name + ".py")).read


# ------------------------------------------------------------------ devices


def device_check(chips: int) -> Dict:
    """The devices this cell runs on, or :class:`NoChip`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's default device is {devs[0].platform!r}, not a "
                     "TPU: this benchmark measures nothing elsewhere")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileWatch:
    """Counts compilations, persistent-cache hits and traces while on."""

    EVENTS = {
        "/jax/core/compile/backend_compile_duration": "compiles",
        "/jax/core/compile/jaxpr_trace_duration": "traces",
    }

    def __init__(self):
        import jax.monitoring as mon

        self.on = False
        self.counts = {"compiles": 0, "cache_hits": 0, "traces": 0}
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def _event(self, event: str, **_kw) -> None:
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1


def span(name: str):
    """A host span in the profiler's trace (cheap when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


# --------------------------------------------------------------------- a run


@dataclasses.dataclass
class Check:
    """One compared number beside its limit (``value <= limit`` passes)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class LayerInput:
    """What a per-layer metric's reader gets."""

    trace: Any                      # trace_reduce.Trace
    counters: Dict[str, float]      # the cell driver's window counters
    cell: Cell
    peaks: Dict[str, float]


def peaks_for(kind: str) -> Dict[str, float]:
    table = read_json("peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: Dict, log: Callable[[str], None]) -> Dict:
    """Set up, measure, check; returns the result line's object."""
    from repro.runtime.compile_cache import enable_persistent_cache

    import trace_reduce

    log(f"compile cache: {enable_persistent_cache()}")
    driver = cell.driver
    watch = CompileWatch()
    state = driver.setup(cell, seed, span, log)
    setup_s = time.perf_counter() - T_PROCESS
    log(f"set-up {setup_s:.3f}s")

    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    import jax

    # Device ops and TraceAnnotation spans only: the Python tracer records
    # every Python call and would slow the host path it is meant to show.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    watch.on = True
    with (jax.profiler.trace(logdir, profiler_options=opts) if trace
          else contextlib.nullcontext()):
        with span("window"):
            window = driver.window(state, seconds, span, log)
    watch.on = False
    log("inside the window: " + ", ".join(
        f"{k} {v}" for k, v in watch.counts.items()))
    mem_peak = memory_peak_bytes(cell.chips)

    metrics: Dict[str, Dict] = {}
    breakdown = None
    dev = dict(device, memory_peak_bytes=mem_peak)
    if trace:
        t = trace_reduce.load(logdir, devices=cell.chips)
        shutil.rmtree(logdir, ignore_errors=True)
        summ = trace_reduce.summary(t)
        dev.update(busy_s=summ["busy_s"], window_s=summ["window_s"])
        breakdown = {"device_ops": [list(kv) for kv in summ["device_ops"]],
                     "idle_gaps": [list(kv) for kv in summ["idle_gaps"]]}
        inp = LayerInput(trace=t, counters=window["counters"], cell=cell,
                         peaks=peaks_for(device["kind"]))
        for m in cell.per_layer:
            value = metric_reader(m["name"])(inp)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = (setup_s if m["name"] == "setup_s"
                     else window["metrics"][m["name"]])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for k, v in window["counters"].items():
        log(f"counter {k} {v}")

    outcome = driver.check(state, window, log)
    del state, window
    checks = [Check(*c) for c in outcome["checks"]]
    correct = (outcome["failed"] == 0 and outcome["attempted"] > 0
               and all(c.ok for c in checks))
    out = {"correct": correct, "attempted": outcome["attempted"],
           "failed": outcome["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be a whole number >= 0")

    cell = resolve(load_manifest(), args.workload)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    try:
        device = device_check(cell.chips)
    except NoChip as e:
        log(f"bench: {e}")
        return EXIT_NO_CHIP
    log(f"bench: {args.workload} seed {args.seed} on {device['count']} x "
        f"{device['kind']}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                   log)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
