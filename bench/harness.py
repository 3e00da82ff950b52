"""The cell benchmark's library: find a cell's files by name, check the
device, keep the compile cache in the checkout, run the cell, and assemble
its result line.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json``  — the model and engine settings;
* ``bench/traffic/<mix>.json``     — the mix's parameters (``traffic.py``)
  and the driver that runs it;
* ``bench/drivers/<driver>.py``    — the window and the comparison;
* ``bench/workloads/<cell>.json``  — the cell's comparison limits;
* ``bench/metrics/<metric>.py``    — one reader, ``read(run)``;
* ``bench/{systems,reference,flops}/<family>.py`` — per model family.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import logging
import os
import shutil
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

OUT_DIR = ".bench_out"          # traces and scratch, ignored by git
CACHE_DIR = ".jax_cache"        # the persistent compile cache


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (names may hold dots, as metric names do)."""
    name = "bench_dyn_" + "_".join(path.with_suffix("").parts[-2:]) \
        .replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Spec:
    """One cell, as its files describe it."""
    root: Path
    bench: Dict
    cell: Dict
    workload: Dict
    config: Dict
    traffic: Dict

    @property
    def name(self) -> str:
        return self.cell["name"]

    @property
    def model_cfg(self) -> Dict:
        return self.config["model"]

    def family_module(self, kind: str):
        return load_module(self.root / "bench" / kind
                           / f"{self.config['family']}.py")

    def metrics(self, section: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.bench[section]
                if self.name in m.get("workloads", [self.name])]

    def driver(self, seed: int, seconds: float):
        """The mix's driver (``bench/drivers/<driver>.py``) for this run."""
        mod = load_module(self.root / "bench" / "drivers"
                          / f"{self.traffic['driver']}.py")
        return mod.Driver(self, seed, seconds)

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")


def load_spec(root: Path, cell: str) -> Spec:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if cell not in cells:
        raise SystemExit(f"unknown workload {cell!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    c = cells[cell]
    conf = {x["name"]: x for x in bench["configs"]}[c["config"]]
    return Spec(root=root, bench=bench, cell=c,
                workload=_load_json(root / "bench" / "workloads"
                                    / f"{cell}.json"),
                config=_load_json(root / conf["file"]),
                traffic=_load_json(root / "bench" / "traffic"
                                   / f"{c['traffic']}.json"))


def check_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU, JAX found platform "
                     f"{devs[0].platform!r} ({devs[0].device_kind}); there "
                     f"is no fallback")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


def enable_cache(root: Path) -> str:
    """Every program, however small or quick to compile, goes to the
    persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


class CompileCount(logging.Handler):
    """Backend compiles (persistent-cache loads included) since creation,
    and, while ``naming`` is on, the names JAX logs for them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        super().__init__(logging.WARNING)
        self.n = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" with global shapes")[0][10:])

    @contextlib.contextmanager
    def naming(self):
        import jax
        log = logging.getLogger("jax")
        log.addHandler(self)
        jax.config.update("jax_log_compiles", True)
        try:
            yield
        finally:
            jax.config.update("jax_log_compiles", False)
            log.removeHandler(self)


def peak_table(root: Path, kind: str) -> Dict:
    peaks = _load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json ({sorted(peaks)})")
    return peaks[kind]


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    spec: Spec
    cell: Any                  # the driver (bench/drivers/<driver>.py)
    setup_s: float
    window_s: float
    trace: Optional[Dict]      # trace_reduce.reduce_events output
    peak: Optional[Dict]       # bench/peaks.json entry of the device
    flops: Any                 # bench/flops/<family>.py


def read_metrics(run: Run, section: str) -> Dict[str, Dict]:
    out = {}
    for m in run.spec.metrics(section):
        v = run.spec.reader(m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def verdict(checks: Dict[str, Dict]) -> bool:
    """``correct``: every number compared within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(root: Path, cell: str, seed: int, seconds: float, trace: bool,
             *, t_start: float, require_tpu: bool = True,
             use_cache: bool = True, control: bool = False) -> Dict:
    """One run of one cell; returns the result object (printed by run.py).
    ``control`` puts the control in the program's place for the comparison
    (its reading is what ``correct`` is decided on), to show that the
    comparison fails it; the benchmark's own runs never set it."""
    spec = load_spec(root, cell)
    devs = check_devices(spec.cell["chips"], require_tpu)
    import jax
    if use_cache:
        enable_cache(root)
    clock = CompileCount()
    peak = peak_table(root, devs[0].device_kind) if require_tpu else None
    drv = spec.driver(seed, seconds)
    drv.setup()
    out_dir = root / OUT_DIR / f"{cell}.{seed}"
    if trace:
        shutil.rmtree(out_dir, ignore_errors=True)
        out_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only, no Python calls
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    c0 = clock.n
    with clock.naming():
        t0, t1 = drv.window()
    window_compiles = clock.n - c0
    if window_compiles:
        print(f"bench: compiled in the window: {clock.names}",
              file=sys.stderr)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
    drv.drain()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:spec.cell["chips"]])
    if trace:
        from bench import trace_reduce
        xs = sorted(out_dir.rglob("*.xplane.pb"))
        devices, host = trace_reduce.load_xspace(str(xs[-1]))
        reduced = trace_reduce.reduce_events(devices, host)
        shutil.rmtree(out_dir, ignore_errors=True)
    run = Run(spec, drv, setup_s=t0 - t_start, window_s=t1 - t0,
              trace=reduced, peak=peak, flops=spec.family_module("flops"))
    metrics = read_metrics(run, "per_layer" if trace else "end_to_end")
    attempted, failed = drv.tally()
    compared = drv.compare(control)
    checks = drv.checks(compared, control)
    checks["window_compiles"] = {"value": window_compiles, "limit": 0}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result: Dict[str, Any] = {"correct": verdict(checks),
                              "attempted": attempted, "failed": failed,
                              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared
    result["checks"] = checks
    return result


__all__ = ["run_cell", "verdict", "load_spec", "Spec", "Run", "NoChip", "load_module",
           "check_devices", "enable_cache", "read_metrics"]
