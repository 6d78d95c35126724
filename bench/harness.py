"""The benchmark's general part: finds a cell's pieces by name and runs it.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives:

  * ``bench/configs/<config>.json``  sizes, model settings, guarantees;
  * ``bench/traffic/<traffic>.json`` a traffic mix: its driver and the
    driver's parameters;
  * ``bench/drivers/<driver>.py``    a generic driver (``Driver`` class);
  * ``bench/limits/<workload>.json`` the limit of each number the cell's
    correctness check compares (set from the cell's own readings);
  * ``bench/metrics/<metric>.py``    one reader per per-layer metric.

A run: the driver sets up (data, caps, compiles, warm-up), measures for
``seconds``, the peak device memory is read, the readers of a traced run
take their metrics, the driver frees the program's state and runs the
plain reference, and one JSON result line is assembled.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The benchmark cannot run this cell here (and prints no result)."""


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def _json(kind: str, name: str, bench: pathlib.Path) -> dict:
    path = bench / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def load_config(name: str, bench: pathlib.Path = BENCH) -> dict:
    return _json("configs", name, bench)


def load_traffic(name: str, bench: pathlib.Path = BENCH) -> dict:
    return _json("traffic", name, bench)


def load_limits(workload: str, bench: pathlib.Path = BENCH) -> dict:
    return _json("limits", workload, bench)


def compare(readings: dict, limits: dict, log=None) -> list[dict]:
    """Each limited reading beside its limit; a reading with no limit is
    logged, not compared, and a limit with no reading is an error."""
    missing = limits.keys() - readings.keys()
    if missing:
        raise BenchError(f"no reading for limit(s) {sorted(missing)}")
    for k in sorted(readings.keys() - limits.keys()):
        if log:
            log(f"reading {k}={readings[k]!r} (not compared)")
    return [{"name": k, "value": readings[k], "limit": limits[k]}
            for k in limits]


def load_driver(name: str):
    """The ``Driver`` class of ``bench/drivers/<name>.py``."""
    return importlib.import_module(f"bench.drivers.{name}").Driver


def load_reader(metric: str, bench: pathlib.Path = BENCH):
    """The ``read(rec)`` function of ``bench/metrics/<metric>.py``."""
    path = bench / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path} for metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(spec: dict, cell: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
    return [m for m in spec[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def load_peaks(bench: pathlib.Path = BENCH) -> dict:
    return json.loads((bench / "peaks.json").read_text())


def check_devices(devices, chips: int, peaks: dict) -> dict:
    """The peak entry of the chip, or a ``BenchError``: no TPU, fewer
    chips than the cell asks for, or a chip the peaks table lacks."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        raise BenchError(f"no TPU: JAX platform is {plat!r}")
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chip(s), JAX sees "
                         f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        raise BenchError(f"device_kind {kind!r} is not in bench/peaks.json")
    return peaks["devices"][kind]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """Seconds and count of XLA compiles, from JAX's own compile events
    (copied from chip_smoke.py's listener)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def device_info(devices, chips: int) -> dict:
    used = devices[:chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}


def run_cell(spec: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, devices, peak: dict, t_start: float,
             bench: pathlib.Path = BENCH, scale: float = 1.0) -> dict:
    """Run one cell and return its result line (as a dict). ``scale`` < 1
    subsamples the data, for tests on the CPU."""
    import jax

    from bench import trace_reduce

    config = load_config(cell["config"], bench)
    traffic = load_traffic(cell["traffic"], bench)
    limits = load_limits(cell["name"], bench)
    clock = CompileClock()
    driver = load_driver(traffic["driver"])(config, traffic, seed, log,
                                            scale=scale)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s!r} compile_s={clock.seconds!r} "
        f"compiles={clock.count}")

    compiles0 = clock.count
    tracer = trace_reduce.Tracer() if trace else None
    if tracer:
        tracer.start()
    with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX +
                                      trace_reduce.WINDOW_SPAN):
        window = driver.window(seconds)
    trace_data = tracer.stop() if tracer else None
    if trace_data:
        log(f"trace: device_events={trace_data['device_events']} planes: "
            + " | ".join(trace_data["layout"]))
    log(f"window_s={window['window_s']!r} compiles_in_window="
        f"{clock.count - compiles0}")
    for line in window.get("notes", []):
        log(line)
    device = device_info(devices, cell["chips"])

    if trace:
        rec = {"spans": window["spans"], "counters": window["counters"],
               "objects": driver.objects(), "trace": trace_data,
               "peak": peak, "window_s": window["window_s"]}
        metrics = {}
        for m in metrics_of(spec, cell, "per_layer"):
            value = load_reader(m["name"], bench)(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace_data["busy_s"]
        device["window_s"] = trace_data["window_s"]
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        metrics = {}
        for m in metrics_of(spec, cell, "end_to_end"):
            if m["name"] not in values:
                raise BenchError(f"driver {traffic['driver']!r} did not "
                                 f"report {m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # frees the program's state, then runs the reference
    checks = compare(driver.check(), limits, log)
    correct = bool(checks) and all(c["value"] <= c["limit"] for c in checks)
    for c in checks:
        log(f"check {c['name']} value={c['value']!r} limit={c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = trace_data["breakdown"]
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result
