"""Tests of the benchmark harness on the CPU, at tiny sizes.

    python -m pytest bench/tests -q

JAX runs on the CPU with four virtual devices, so that the four-chip cell's
layout runs too; Pallas kernels run in interpret mode.
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# Each cell cut to a size that the CPU runs in a second or two.  Fits stop at
# a tolerance scaled to the smaller gradient sums; every other setting,
# limits included, is the cell's own.
TINY = {
    "logreg_higgs.newton": {"rows": 40_000, "solver.tol": 1e-3},
    "logreg_higgs_x4.newton": {"rows": 64_000, "solver.tol": 1e-3},
    "dgemm_16k.pallas": {"n": 512, "check.rows": 32},
}


def make_root(dest: Path, cells=TINY) -> Path:
    """A checkout at ``dest`` whose cells ``tiny_<cell>`` run the same job
    code as ``<cell>`` at the sizes of ``cells``, added as new files and
    ``BENCHMARK.json`` entries only."""
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    (dest / "src").symlink_to(ROOT / "src")
    spec = json.loads((dest / "BENCHMARK.json").read_text())
    for cell, sizes in cells.items():
        workload = json.loads((dest / "bench" / "workloads" / f"{cell}.json").read_text())
        config = json.loads((dest / "bench" / "configs" / f"{workload['config']}.json").read_text())
        for key, value in sizes.items():
            *path, last = key.split(".")
            node = config
            for part in path:
                node = node[part]
            node[last] = value
        name, tiny = f"tiny_{workload['config']}", f"tiny_{cell}"
        workload["config"] = name
        (dest / "bench" / "configs" / f"{name}.json").write_text(json.dumps(config))
        (dest / "bench" / "workloads" / f"{tiny}.json").write_text(json.dumps(workload))
        spec["workloads"].append({"name": tiny, "config": name, "traffic": "tiny",
                                  "chips": config["chips"], "why": "tiny"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if cell in m.get("workloads", []):
                m["workloads"].append(tiny)
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dest


@pytest.fixture
def make_checkout():
    return make_root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    return make_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture
def run_tiny(tiny_root):
    """Run ``tiny_<cell>`` once below the harness's look for a chip; return
    its result line."""
    import time

    from bench import harness
    from repro.backend import GLOBAL_COMPILE_CACHE

    def run(cell, seconds=0.3, trace=False, seed=2**31 + 11):
        # a fault planted by a test must not meet kernels compiled before it
        GLOBAL_COMPILE_CACHE.clear()
        result, _ = harness.run_cell(tiny_root, f"tiny_{cell}", seed, seconds,
                                     trace, time.perf_counter())
        return result

    yield run
    GLOBAL_COMPILE_CACHE.clear()
