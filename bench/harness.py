"""Runs one cell of ``BENCHMARK.json`` once: set-up, the measured window,
the check against the plain reference, and the result line.

Everything is found by name: the cell names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``); the
mix names a driver (``drivers/<driver>.py``); each per-layer metric is read
by ``metrics/<metric>.py``.  Adding a cell, a mix, a driver or a metric is
adding files and entries, never editing one.

A driver module defines ``Driver(run)`` with ``setup()``, ``window(seconds)``,
``after_window()``, ``end_to_end()``, ``layer_data()``, ``release()`` and
``check()``; see ``drivers/train.py`` for the smallest one.  A metric module
defines ``read(ctx)``, which returns a number or ``None`` when the run holds
nothing for it to read.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

ROOT = Path(__file__).resolve().parent          # the benchmark's directory
CHECKOUT = ROOT.parent
SPEC = CHECKOUT / "BENCHMARK.json"

WINDOW_SPAN = "bench.window"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_start() -> float:
    """This process's start on the ``time.perf_counter`` clock (Linux
    ``/proc``; elsewhere the time this module was imported)."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return _IMPORTED


_IMPORTED = time.perf_counter()


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys replaced, nested dicts merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_json(root: Path, kind: str, name: str) -> dict:
    return json.loads((root / kind / f"{name}.json").read_text())


def load_module(root: Path, kind: str, name: str):
    path = root / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Run:
    """What a driver and a metric reader see of one run."""

    cell: dict
    model: dict            # configs/<config>.json, smoke overrides applied
    mix: dict              # traffic/<mix>.json, smoke overrides applied
    seed: int
    seconds: float
    trace: bool
    device_kind: str
    peaks: Optional[dict]
    smoke: bool = False
    options: dict = field(default_factory=dict)   # calibration: "control"
    root: Path = ROOT      # where configs, traffic, drivers, metrics live


@dataclass
class MetricContext:
    run: Run
    data: dict             # the driver's layer_data()
    trace: Any             # trace_reduce.Summary or None


class CompileCounter:
    """XLA compiles and persistent-cache reads while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax
        self.counts = {e.rsplit("/", 1)[-1]: 0 for e in self.EVENTS}
        self.active = False
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        self._event(event)

    def _event(self, event, **_kw):
        if self.active and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[-1]] += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._duration)
        self._jax.monitoring.unregister_event_listener(self._event)


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` when set,
    else at ``<checkout>/.jax_cache``; every program is cached, however
    small or quick to compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices(chips: int, require_chip: bool) -> list:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator (platform cpu); the benchmark "
                     "measures the chip and does not fall back to the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def holds(check: dict) -> bool:
    """A compared number within its limit: at most the limit, or at least
    it where the check says ``at_least``."""
    if check.get("at_least"):
        return check["value"] >= check["limit"]
    return check["value"] <= check["limit"]


def cell_metrics(spec: dict, group: str, cell: str) -> list:
    """Metrics of ``group`` (``end_to_end`` or ``per_layer``) that ``cell``
    reports."""
    return [m for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]]


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             spec: Optional[dict] = None, root: Path = ROOT,
             smoke: bool = False, require_chip: bool = True,
             t_start: Optional[float] = None, options: Optional[dict] = None,
             mix_overrides: Optional[dict] = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``smoke`` applies the configuration's and the mix's smoke-size
    overrides and ``require_chip=False`` lets it run on the CPU (tests);
    ``options`` and ``mix_overrides`` serve calibration and the rate sweep,
    never the benchmark's own runs.  ``options["control"]`` names a control
    that the driver's check puts in the program's place, so that the run is
    judged on the control's readings and comes out not correct."""
    t_start = process_start() if t_start is None else t_start
    spec = spec if spec is not None else json.loads(SPEC.read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    model = load_json(root, "configs", cell["config"])
    mix = load_json(root, "traffic", cell["traffic"])
    if smoke:
        model = merged(model, model.get("smoke", {}))
        mix = merged(mix, mix.get("smoke", {}))
    mix = merged(mix, mix_overrides or {})

    devs = devices(cell["chips"], require_chip)
    import jax
    from counts import peaks
    import trace_reduce

    if require_chip:
        enable_compile_cache()
    kind = devs[0].device_kind
    run = Run(cell=cell, model=model, mix=mix, seed=seed, seconds=seconds,
              trace=trace, device_kind=kind,
              peaks=peaks(kind) if require_chip else None, smoke=smoke,
              options=options or {}, root=root)
    driver = load_module(root, "drivers", mix["driver"]).Driver(run)
    driver.setup()
    setup_s = time.perf_counter() - t_start

    counter = CompileCounter()
    tdir = CHECKOUT / ".bench_trace" / f"{name}.{seed}"
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir), profiler_options=_trace_options())
    counter.active = True
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            driver.window(seconds)
    finally:
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
    counter.close()
    driver.after_window()

    stats = [d.memory_stats() or {} for d in devs]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    e2e = driver.end_to_end()
    data = driver.layer_data()
    summary = None
    if trace:
        summary = trace_reduce.reduce(tdir, WINDOW_SPAN, len(devs))
        shutil.rmtree(tdir, ignore_errors=True)
    driver.release()
    gc.collect()
    checks, attempted, failed = driver.check()
    correct = failed == 0 and all(holds(c) for c in checks.values())

    if trace:
        ctx = MetricContext(run=run, data=data, trace=summary)
        metrics = {}
        for m in cell_metrics(spec, "per_layer", name):
            value = load_module(root, "metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(spec, "end_to_end", name)}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    if "load" in data:
        out["load"] = data["load"]
    out["setup_phases"] = getattr(driver, "setup_phases", {})
    out["compiles_in_window"] = counter.counts
    out["setup_s"] = setup_s
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    import argparse
    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"compiles in the window: {json.dumps(out['compiles_in_window'])}; "
          f"set-up {out['setup_s']:.3f} s "
          f"{json.dumps(out.get('setup_phases', {}))}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
