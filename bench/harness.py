"""One run of one cell: set-up, warm-up, the measured window, the check.

A cell is found by its name and nothing else:

- ``BENCHMARK.json``: the cell's chips, and which metrics it reports;
- ``bench/workloads/<cell>.json``: its configuration, job kind and traffic;
- ``bench/configs/<config>.json``: the deployment, as it is run;
- ``bench/jobs/<kind>.py``: set-up, one job, the algorithm's counts, the
  plain reference and the comparison (protocol in ``bench/jobs/__init__.py``);
- ``bench/metrics/<metric>.py``: ``read(run)``, one number or ``None``.

The window is a closed loop: whole jobs, one at a time, back to back, until
``seconds`` have passed; the job running then is finished and counted.
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import tempfile
import traceback
from dataclasses import dataclass
from pathlib import Path
from time import perf_counter
from typing import Any, Dict, List, Optional, Tuple

from bench.trace_reduce import TraceSummary, find_xplane, reduce_trace, span

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import the file at ``path`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(root: Path, device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a device not in the table is an error."""
    table = load_json(root / "bench" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (has {sorted(table)})")
    return table[device_kind]


@dataclass
class Check:
    """One number compared with its limit: the run is correct only if the
    number is finite and at most the limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def is_correct(checks: List[Check], failed: int) -> bool:
    """The verdict of a run: some number was compared, no job failed, and
    every compared number is within its limit."""
    return bool(checks) and failed == 0 and all(c.ok for c in checks)


@dataclass
class Cell:
    name: str
    chips: int
    workload: Dict[str, Any]
    config: Dict[str, Any]
    kind: Any  # the job module
    metrics: List[Dict[str, Any]]  # every metric entry this cell reports

    @classmethod
    def load(cls, root: Path, name: str) -> "Cell":
        spec = load_json(root / "BENCHMARK.json")
        entries = [w for w in spec["workloads"] if w["name"] == name]
        if len(entries) != 1:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json")
        workload = load_json(root / "bench" / "workloads" / f"{name}.json")
        config = load_json(root / "bench" / "configs" / f"{workload['config']}.json")
        kind = load_module(root / "bench" / "jobs" / f"{workload['kind']}.py")
        return cls(name, entries[0]["chips"], workload, config, kind,
                   reported_metrics(spec, name))

    def metric_entries(self, trace: bool) -> List[Dict[str, Any]]:
        section = "per_layer" if trace else "end_to_end"
        return [m for m in self.metrics if m["section"] == section]


def reported_metrics(spec: Dict[str, Any], cell: str) -> List[Dict[str, Any]]:
    """The end-to-end metrics listed for ``cell`` (all cells where a metric
    has no ``workloads``), then the per-layer metrics whose ``workloads``
    list it; every per-layer metric carries that list."""
    e2e = [dict(m, section="end_to_end") for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    layer = [dict(m, section="per_layer") for m in spec["per_layer"]
             if cell in m["workloads"]]
    return e2e + layer


class CompileCounter:
    """Counts the executables JAX compiles or loads from its persistent cache
    while ``on`` is set, from JAX's own monitoring events."""

    def __init__(self) -> None:
        self.count = 0
        self.on = False

    def __call__(self, event: str, *args, **kwargs) -> None:
        if self.on and event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


@dataclass
class Run:
    """What one run measured; the metric readers take their numbers from it."""

    cell: Cell
    job: Any
    setup_s: float
    window_s: float
    records: List[Dict[str, Any]]
    loads0: Dict[str, float]
    loads1: Dict[str, float]
    compiles: int
    peak_bytes: Optional[int]
    peaks: Optional[Dict[str, float]] = None
    trace: Optional[TraceSummary] = None

    @property
    def jobs(self) -> int:
        return len(self.records)

    def per_job(self, key: str) -> Optional[float]:
        """Mean of a job record's ``key`` over the window's jobs."""
        vals = [r[key] for r in self.records if key in r]
        return sum(vals) / len(vals) if vals else None

    def counter_per_job(self, key: str) -> Optional[float]:
        """A library counter's growth over the window, per job."""
        if key not in self.loads0 or key not in self.loads1:
            return None
        return (self.loads1[key] - self.loads0[key]) / self.jobs

    def roofline_share(self) -> Optional[float]:
        """Percent of the chips' busy time that the algorithm's least device
        time fills: for each job the larger of its operations over the peak
        bf16 rate and its bytes over the peak HBM rate, split over the chips,
        summed over the traced window's jobs."""
        if self.trace is None or self.peaks is None:
            return None
        least = 0.0
        for rec in self.records:
            flops, nbytes = self.job.counts(rec)
            least += max(flops / self.peaks["bf16_flops_per_s"],
                         nbytes / self.peaks["hbm_bytes_per_s"])
        busy = sum(self.trace.busy_s.values())
        return 100.0 * least / busy if busy > 0 else None

    def idle_share(self) -> Optional[float]:
        if self.trace is None:
            return None
        return 100.0 * self.trace.idle_share()


def peak_bytes(chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest of the first ``chips`` devices, or
    ``None`` where the backend reports no memory statistics."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def device_info(chips: int, peak: Optional[int]) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak or 0}


def _window(job, seconds: float, trace_dir: Optional[str],
            counter: CompileCounter) -> Tuple[float, List[Dict[str, Any]]]:
    import jax

    records: List[Dict[str, Any]] = []
    times: List[float] = []
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host timers read as they do untraced
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        counter.on = True
        with span("window"):
            t0 = t = perf_counter()
            while True:
                with span("job"):
                    records.append(job.run())
                now = perf_counter()
                times.append(now - t)
                t = now
                if t - t0 >= seconds:
                    break
            window_s = t - t0
        counter.on = False
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    times.sort()
    print(f"window: {len(times)} jobs in {window_s!r} s; seconds per job: "
          f"min {times[0]!r}, median {times[len(times) // 2]!r}, "
          f"max {times[-1]!r}", file=sys.stderr, flush=True)
    return window_s, records


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: Optional[Dict[str, float]] = None,
             ) -> Tuple[Dict[str, Any], List[Check]]:
    """Run cell ``name`` once; return its result line and its checks.

    ``t_start`` is the process's start on the ``perf_counter`` clock, so
    that ``setup_s`` runs from it to the first timed job.  ``peaks`` is the
    device's row of ``bench/peaks.json`` (needed by roofline shares only).
    """
    cell = Cell.load(root, name)
    records: List[Dict[str, Any]] = []
    peak = None
    try:
        job = cell.kind.setup(cell.config, cell.workload["traffic"], seed)
        for _ in range(cell.workload["traffic"]["warmup_jobs"]):
            job.run()
        loads0 = job.loads()
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        try:
            with CompileCounter() as counter:
                setup_s = perf_counter() - t_start
                window_s, records = _window(job, seconds, trace_dir, counter)
            loads1 = job.loads()
            peak = peak_bytes(cell.chips)
            summary = (reduce_trace(find_xplane(trace_dir), cell.chips)
                       if trace_dir is not None else None)
        finally:
            if trace_dir is not None:
                shutil.rmtree(trace_dir, ignore_errors=True)
        run = Run(cell, job, setup_s, window_s, records, loads0, loads1,
                  counter.count, peak, peaks, summary)
        metrics = {}
        for m in cell.metric_entries(trace):
            reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        job.collect()
        checks, failed = job.check(records)
    except Exception:  # the run's boundary: report the failure, not a crash
        traceback.print_exc()
        attempted = len(records) + 1
        return ({"correct": False, "attempted": attempted, "failed": attempted,
                 "metrics": {}, "device": device_info(cell.chips, peak)}, [])
    result: Dict[str, Any] = {
        "correct": is_correct(checks, failed),
        "attempted": len(records),
        "failed": failed,
        "metrics": metrics,
        "device": device_info(cell.chips, peak),
    }
    if summary is not None:
        result["device"]["busy_s"] = sum(summary.busy_s.values()) / len(summary.busy_s)
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    # a number that is not finite is written null: the line stays JSON
    result["checks"] = {c.name: {"value": c.value if math.isfinite(c.value) else None,
                                 "limit": c.limit} for c in checks}
    return result, checks


def report(result: Dict[str, Any], checks: List[Check]) -> None:
    """Each compared number beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    out, err = sys.stdout, sys.stderr
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=err, flush=True)
    if not checks:
        print("check: no comparison was made", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
