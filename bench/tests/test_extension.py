"""A later change adds a configuration, a cell and a per-layer metric as new
files and new ``BENCHMARK.json`` entries, and edits no file that is there;
the harness finds them by name.  ``bench/run.py`` itself refuses a device
that is not a TPU."""
import hashlib
import json
import subprocess
import sys
import time
from pathlib import Path

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def _digests(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_config_cell_and_metric_are_found_by_name(tmp_path, make_checkout):
    root = make_checkout(tmp_path)
    before = _digests(root)
    spec_before = json.loads((root / "BENCHMARK.json").read_text())

    config = json.loads((root / "bench" / "configs" / "tiny_logreg_higgs.json").read_text())
    config.update(rows=24_000, features=12, sep=0.5)
    config["layout"]["row_blocks"] = 4
    (root / "bench" / "configs" / "small_logreg.json").write_text(json.dumps(config))
    (root / "bench" / "workloads" / "small_logreg.newton.json").write_text(json.dumps({
        "config": "small_logreg", "kind": "newton_fit",
        "traffic": {"warmup_jobs": 1},
        "why": "a small fit with 12 features"}))
    (root / "bench" / "metrics" / "plan_hits_per_fit.py").write_text(
        "def read(run):\n    return run.counter_per_job('plan_hits')\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "small_logreg", "source": "a test",
                            "file": "bench/configs/small_logreg.json",
                            "reduced": ["rows"], "why": "a test"})
    spec["workloads"].append({"name": "small_logreg.newton", "config": "small_logreg",
                              "traffic": "newton", "chips": 1, "why": "a test"})
    spec["end_to_end"][0]["workloads"].append("small_logreg.newton")
    spec["per_layer"].append({"name": "plan_hits_per_fit", "unit": "hits",
                              "better": "higher", "source": "program_counter",
                              "layer": "placement", "moves": "solve_s",
                              "workloads": ["small_logreg.newton"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    results = {}
    for trace in (False, True):
        results[trace], _ = harness.run_cell(root, "small_logreg.newton", 5, 0.3,
                                             trace, time.perf_counter())
        assert results[trace]["correct"], results[trace]
    # the CPU reports no device memory, so peak_hbm_gb is left out
    assert set(results[False]["metrics"]) == {"solve_s", "setup_s"}
    layer = results[True]["metrics"]
    assert layer["plan_hits_per_fit"]["unit"] == "hits"
    assert layer["plan_hits_per_fit"]["value"] > 0
    assert "cross_chip_moves_per_fit" not in layer

    # nothing that was there changed, only entries were added
    after = _digests(root)
    assert {k: after[k] for k in before} == before
    spec_after = json.loads((root / "BENCHMARK.json").read_text())
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(spec_before[section], spec_after[section]):
            assert {k: v for k, v in new.items() if k != "workloads"} == \
                {k: v for k, v in old.items() if k != "workloads"}


def test_command_refuses_a_cpu():
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "logreg_higgs.newton", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, capture_output=True, text=True, timeout=300,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "no TPU: JAX's first device is cpu" in r.stderr
