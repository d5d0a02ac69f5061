"""Run one cell of ``BENCHMARK.json`` and print its result line.

A cell names a configuration (``bench/configs/<name>.json``) and a traffic
mix (``bench/traffic/<name>.json``); the configuration's ``driver`` key
names the engine driver (``bench/drivers/<driver>.py``), and each per-layer
metric is read by ``bench/metrics/<metric>.py``. Limits of the correctness
comparison live in ``bench/limits/<cell>.json``. Nothing here names a cell:
a new cell is new files plus new ``BENCHMARK.json`` entries.

A run is: set-up (weights from the seed, engine, warm-up of the cell's
shapes), one measured window of ``--seconds``, the peak device memory,
the engine freed, then the comparison with the plain reference. With
``--trace 1`` the profiler records a sub-window in the middle of the
window, and the per-layer metrics are printed instead of the end-to-end
ones.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# Longest traced sub-window; the rest of the window runs untraced.
TRACE_SECONDS = 8.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=load_json(ROOT / conf["file"]),
                traffic_name=w["traffic"],
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(BENCH / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_class(cell: Cell):
    kind = cell.config["driver"]
    return load_module(BENCH / "drivers" / f"{kind}.py",
                       f"bench_driver_{kind}").Driver


def accelerator(chips: int):
    """The devices the cell runs on; raises :class:`NoChip` off the chip."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device: {e}") from e
    if devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator (platform 'cpu')")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def device_record(devs) -> dict:
    d0 = devs[0]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile ``q`` in [0, 100] of ``values``."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class Tracer:
    """Profiler over a sub-window in the middle of the measured window.

    The driver calls :meth:`tick` with the seconds since the window opened
    between engine calls; the trace starts at ``offset`` and stops after
    ``length`` seconds, so every engine call it records ran whole inside
    it. ``active`` tells the driver which calls to count for the per-layer
    readers, and :meth:`span` wraps the driver's own calls.

    ``host`` sets whether the profiler records the host's spans. A driver
    whose dispatches copy large inputs to the chip turns it off: the TPU
    runtime then traces every chunk of the copy, which slows the host
    tenfold. Its spans are kept here instead and placed on the device's
    clock when the trace is read.
    """

    def __init__(self, enabled: bool, seconds: float, logdir: Path | None,
                 host: bool = True):
        self.enabled = enabled
        self.length = min(TRACE_SECONDS, seconds / 2)
        self.offset = (seconds - self.length) / 2
        self.logdir = logdir
        self.host = host
        self.active = False
        self.done = False
        self.spans = []            # (name, start, end) on the host's clock
        self._span = None

    def tick(self, t: float) -> bool:
        if not self.enabled or self.done:
            return False
        import jax

        if not self.active and t >= self.offset:
            shutil.rmtree(self.logdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1 if self.host else 0
            jax.profiler.start_trace(str(self.logdir), profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.traced")
            self._span.__enter__()
            self._t0 = time.perf_counter()
            self.active = True
        elif self.active and t >= self.offset + self.length:
            self.stop()
        return self.active

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        if self.active:
            self.spans.append((name, t0, time.perf_counter()))

    def stop(self):
        if not self.active:
            return
        import jax

        self.spans.append(("bench.traced", self._t0, time.perf_counter()))
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def path(self) -> str | None:
        found = glob.glob(str(self.logdir / "**" / "*.xplane.pb"),
                          recursive=True)
        return found[0] if found else None


@dataclasses.dataclass
class Window:
    """What a driver's measured window returns."""
    metrics: dict                 # end-to-end metric name -> value
    attempted: int
    failed: int
    counters: dict                # whole-window counters for the readers
    traced: dict                  # counters of the calls inside the trace


@dataclasses.dataclass
class Run:
    """Everything a per-layer reader may read."""
    cell: Cell
    window: Window
    trace: object | None          # bench.trace.Trace of the sub-window
    device_kind: str


def read_per_layer(cell: Cell, run: Run) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def emit(result: dict, checks: list) -> None:
    """The compared numbers last on stderr, the result line last on stdout
    (its ``checks`` key last)."""
    result = dict(result, checks={c["name"]: {"value": c["value"],
                                              "limit": c["limit"]}
                                  for c in checks})
    sys.stdout.flush()
    for c in checks:
        print(f"check {c['name']} = {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['value'] <= c['limit'] else 'FAIL'})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def compile_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout."""
    import jax

    path = ROOT / ".jax_cache"
    path.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devs,
             t_start: float, driver_cls=None) -> tuple[dict, list]:
    """Set up, measure, free, compare. Returns (result line, checks)."""
    import jax

    from bench import trace as trace_mod

    driver = (driver_cls or driver_class(cell))(cell, seed)
    with jax.default_device(devs[0]):
        driver.setup()
        setup_s = time.perf_counter() - t_start
        tracer = Tracer(trace, seconds, ROOT / ".bench_trace",
                        host=driver.TRACE_HOST)
        win = driver.window(seconds, tracer)
        tracer.stop()
        device = device_record(devs)
        driver.free()
        gc.collect()
        checks = driver.check()
    result = {"correct": all(c["value"] <= c["limit"] for c in checks),
              "attempted": win.attempted, "failed": win.failed}
    if trace:
        print("bench: traced calls " + json.dumps(
            {k: v if not isinstance(v, list) else len(v)
             for k, v in win.traced.items()}), file=sys.stderr)
        path = tracer.path()
        tr = (trace_mod.load(path, [d.id for d in devs], tracer.spans)
              if path else None)
        if tr is not None and not tr.devices:
            tr = None      # no chip in the trace: nothing a reader can use
        run = Run(cell=cell, window=win, trace=tr,
                  device_kind=devs[0].device_kind)
        result["metrics"] = read_per_layer(cell, run)
        if tr is not None:
            device["busy_s"] = tr.busy_s
            device["window_s"] = tr.window_s
            result["breakdown"] = tr.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = dict(win.metrics, setup_s=setup_s)
        result["metrics"] = {k: {"value": metrics[k], "unit": units[k]}
                             for k in units}
    result["device"] = device
    return result, checks


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    compile_cache()
    try:
        devs = accelerator(cell.chips)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    d0 = devs[0]
    print(f"bench: {cell.name} seed {args.seed} on {len(devs)} x "
          f"{d0.platform} ({d0.device_kind})", file=sys.stderr, flush=True)
    result, checks = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devs, t_start)
    emit(result, checks)
    return 0
