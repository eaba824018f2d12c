"""The per-layer metrics read from the library's span counters: a traced run
of each tiny Newton cell reports every metric the cell lists, the new ones
among them, each non-null.  On the CPU the trace holds no TPU, so the
metrics read from the device's trace are the only ones left out."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SPAN_METRICS = {"drain_ms_per_fit", "dispatch_ms_per_fit", "sync_ms_per_fit",
                "pygc_ms_per_fit"}


def listed(cell):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {m["name"]: m for m in spec["per_layer"] if cell in m["workloads"]}


@pytest.mark.parametrize("cell,new", [
    ("logreg_higgs.newton", SPAN_METRICS),
    ("logreg_higgs_x4.newton", SPAN_METRICS | {"cross_chip_mb_per_fit"}),
])
def test_traced_run_reports_each_listed_metric(run_tiny, cell, new):
    result = run_tiny(cell, trace=True)
    assert result["correct"], result
    metrics = listed(cell)
    assert new <= set(metrics)
    expected = {n for n, m in metrics.items() if m["source"] != "device_trace"}
    assert set(result["metrics"]) == expected
    for name in expected:
        assert result["metrics"][name]["value"] is not None, name
        assert result["metrics"][name]["unit"] == metrics[name]["unit"]
    # drain holds every dispatch; a fit blocks on the device for each
    # gradient norm
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["drain_ms_per_fit"] >= m["dispatch_ms_per_fit"] > 0
    assert m["sync_ms_per_fit"] > 0 and m["pygc_ms_per_fit"] >= 0


def test_cross_chip_bytes_on_four_chips(run_tiny):
    m = run_tiny("logreg_higgs_x4.newton", trace=True)["metrics"]
    assert m["cross_chip_moves_per_fit"]["value"] > 0
    assert m["cross_chip_mb_per_fit"]["value"] > 0
