"""The reduction from a profiler trace to busy and idle time, time per op and
idle gaps; the peaks table; the roofline shares."""
from pathlib import Path

import pytest

from bench import harness
from bench.trace_reduce import TraceSummary, reduce_profile, reduce_trace

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]

# Two chips and a host thread, in nanoseconds from the trace's start:
#   host:  window [1000, 11000); job [1000, 6000) with fit [3500, 5000);
#          job [6000, 10000)
#   TPU 0: module jit_matmul [2000, 4000) holding ops matmul.1 [2000, 3000)
#          and fusion.2 [3000, 4000); op matmul.1 [200, 800) before the
#          window; module jit_add [7000, 9000) with op add.3 [7000, 9000)
#   TPU 1: module jit_matmul [1000, 10500) with op matmul.1 [1000, 10500)
#   TPU 2: not used by a two-chip cell
_PS = 1000  # picoseconds per nanosecond


def _event(meta: int, start_ns: int, end_ns: int) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * _PS} "
            f"duration_ps: {(end_ns - start_ns) * _PS} }}")


def _plane(pid, name, lines, names):
    body = " ".join(
        f'lines {{ id: {i} name: "{ln}" timestamp_ns: 0 '
        + " ".join(_event(*ev) for ev in evs) + " }"
        for i, (ln, evs) in enumerate(lines, 1))
    meta = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
                    for k, v in names.items())
    return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'


SYNTHETIC = " ".join([
    _plane(1, "/host:CPU",
           [("python", [(1, 1000, 11000), (2, 1000, 6000), (3, 3500, 5000),
                        (2, 6000, 10000), (4, 1200, 1300)])],
           {1: "bench:window", 2: "bench:job", 3: "bench:fit",
            4: "PjitFunction(matmul)"}),
    _plane(2, "/device:TPU:0",
           [("XLA Modules", [(1, 2000, 4000), (2, 7000, 9000)]),
            ("XLA Ops", [(3, 200, 800), (3, 2000, 3000), (4, 3000, 4000),
                         (5, 7000, 9000)])],
           {1: "jit_matmul(123)", 2: "jit_add(77)",
            3: "%matmul.1 = f32[8,8] custom-call(a, b)",
            4: "%fusion.2 = f32[8] fusion(x)", 5: "%add.3 = f32[8] add(x, y)"}),
    _plane(3, "/device:TPU:1",
           [("XLA Modules", [(1, 1000, 10500)]),
            ("XLA Ops", [(2, 1000, 10500)])],
           {1: "jit_matmul(123)", 2: "%matmul.1 = f32[8,8] custom-call(a, b)"}),
    _plane(4, "/device:TPU:2",
           [("XLA Ops", [(1, 1000, 11000)])],
           {1: "%matmul.1 = f32[8,8] custom-call(a, b)"}),
])


def _profile(text):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_synthetic_trace_reduces_to_known_numbers():
    s = reduce_profile(_profile(SYNTHETIC), chips=2)
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx({0: 4e-6, 1: 9.5e-6})
    assert s.idle_share() == pytest.approx(1 - (4e-6 + 9.5e-6) / 2 / 10e-6)
    assert s.op_s == pytest.approx({"jit_matmul:matmul": 10.5e-6,
                                    "jit_matmul:fusion": 1e-6,
                                    "jit_add:add": 2e-6})
    # TPU 0 idles [4000, 7000) while fit is open, [9000, 11000) and
    # [1000, 2000) in a job; TPU 1 idles [10500, 11000) after the last job
    assert s.gaps == [("fit", pytest.approx(3e-6)), ("job", pytest.approx(2e-6)),
                      ("job", pytest.approx(1e-6)),
                      ("outside spans", pytest.approx(0.5e-6))]
    assert s.breakdown() == {
        "device_ops": [["jit_matmul:matmul", pytest.approx(10.5e-6)],
                       ["jit_add:add", pytest.approx(2e-6)],
                       ["jit_matmul:fusion", pytest.approx(1e-6)]],
        "idle_gaps": [[name, sec] for name, sec in s.gaps]}


def test_trace_without_tpu_reduces_to_nothing():
    text = _plane(1, "/host:CPU", [("python", [(1, 0, 10)])], {1: "bench:window"})
    assert reduce_profile(_profile(text), chips=1) is None


def test_trace_missing_a_chip_is_an_error():
    with pytest.raises(ValueError, match="of the 4 TPU devices"):
        reduce_profile(_profile(SYNTHETIC), chips=4)


# read from the trace by hand, as a plain union of the op and module events
# inside the window, on a TPU v5 lite
RECORDED = {"window_s": 0.289700981, "busy_s": 0.287719897,
            "matmul_s": 0.273296121, "gap_span": "wait", "gap_s": 0.001067012}


def test_recorded_chip_trace():
    """One 16384^2 product of the dgemm_16k.pallas cell on a TPU v5e, traced
    by the harness: 64 Pallas block products and 48 adds, the chip busy but
    for 2 ms of the window."""
    s = reduce_trace(str(DATA / "dgemm_one_product.xplane.pb"), chips=1)
    assert s.window_s == pytest.approx(RECORDED["window_s"], rel=1e-9)
    assert s.busy_s[0] == pytest.approx(RECORDED["busy_s"], rel=1e-9)
    ops = s.breakdown()["device_ops"]
    assert [name for name, _ in ops] == ["jit_pallas_matmul:matmul", "jit_binary:add"]
    assert ops[0][1] == pytest.approx(RECORDED["matmul_s"], rel=1e-9)
    assert s.gaps[0] == (RECORDED["gap_span"], pytest.approx(RECORDED["gap_s"], rel=1e-9))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind 'TPU v9'"):
        harness.load_peaks(ROOT, "TPU v9")
    assert harness.load_peaks(ROOT, "TPU v5 lite")["bf16_flops_per_s"] == 197e12


class _Job:
    def counts(self, record):
        return record["flops"], record["bytes"]


@pytest.mark.parametrize("op_names", [["jit_matmul:matmul"], ["renamed:kernel", "x:y"]])
def test_roofline_share_comes_from_the_algorithm_counts(op_names):
    """The share divides the algorithm's least device time by busy time: the
    names of the ops in the trace do not enter it."""
    peaks = harness.load_peaks(ROOT, "TPU v5 lite")
    trace = TraceSummary(window_s=2.0, busy_s={0: 1.0, 1: 0.5},
                         op_s={name: 0.75 for name in op_names})
    records = [{"flops": 197e12 * 0.3, "bytes": 819e9 * 0.1},   # compute-bound
               {"flops": 197e12 * 0.1, "bytes": 819e9 * 0.45}]  # memory-bound
    run = harness.Run(cell=None, job=_Job(), setup_s=1.0, window_s=2.0,
                      records=records, loads0={}, loads1={}, compiles=0,
                      peak_bytes=None, peaks=peaks, trace=trace)
    assert run.roofline_share() == pytest.approx(100 * (0.3 + 0.45) / 1.5)
    assert run.idle_share() == pytest.approx(100 * (1 - 0.75 / 2.0))
    run.trace = None
    assert run.roofline_share() is None and run.idle_share() is None
