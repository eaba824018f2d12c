"""Observability stack (core/trace.py + repro.obs): flight-recorder trace,
unified metrics registry, Perfetto export, and critical-path attribution.

Two invariants anchor everything here:

* **Schema stability** — ``ctx.loads()`` is one ``MetricsRegistry.snapshot()``
  whose key list per feature set is golden-tested below; adding a key is a
  deliberate edit to this file, never an accident.
* **Non-interference** — the recorder observes and never mutates: traced runs
  produce bit-identical outputs and *exactly* equal simulated clocks to
  untraced runs, and a fixed chaos seed yields a byte-for-byte identical
  event stream.
"""
import json

import numpy as np
import pytest

from repro.core import ArrayContext, ChaosPlan, ClusterSpec, FlightRecorder
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    analyze,
    export_chrome_trace,
    summary_line,
    top_segments,
)


def make_ctx(k=4, r=2, seed=0, **kw):
    kw.setdefault("backend", "numpy")
    kw.setdefault("pipeline", True)
    return ArrayContext(cluster=ClusterSpec(k, r), node_grid=(k, 1),
                        seed=seed, **kw)


def small_workload(ctx, n=128, d=16, q=8):
    from repro.launch.workloads import logreg_newton_loop

    _g, H, beta = logreg_newton_loop(ctx, n, d, q, iters=2,
                                     reset_loads=False)
    ctx.flush()
    return beta.to_numpy()


# -- golden loads() schema ----------------------------------------------------
# The exact key *sequence* of ctx.loads() per feature set.  These lists are
# the contract downstream consumers (the chip harness under ``bench/``,
# launch drivers, notebook dashboards) parse — extending a stats object must extend
# the matching list here, in provider order.

SUMMARY_KEYS = [
    "max_mem", "max_net_in", "max_net_out", "total_net", "mem_imbalance",
    "objective", "makespan_sync", "makespan_pipelined", "overlap_speedup",
]
RUNTIME_KEYS = [
    "n_rfc", "transfers", "makespan", "pending_ops", "plan_hits",
    "plan_misses", "sched_overhead_s", "dispatch_s", "drain_s", "reshards",
    "reshard_moved", "reshard_s", "layout_bytes", "pygc_s", "pygc_gen2",
]
BACKEND_KEYS = [
    "backend_dispatches", "backend_dispatch_s", "backend_jit_calls",
    "backend_h2d", "backend_d2h", "backend_sync_s", "backend_device_moves",
    "backend_device_move_bytes", "backend_fallbacks", "backend_replays",
    "backend_programs", "backend_lowered_ops", "backend_gram_ops",
]
MEM_KEYS = [
    "mem_capacity", "mem_high_watermark", "mem_low_watermark",
    "mem_live_blocks", "mem_live_elements", "mem_peak_live_elements",
    "mem_peak_store_blocks", "mem_peak_store_bytes", "mem_gc_freed_blocks",
    "mem_gc_freed_elements", "mem_spills", "mem_spill_elements",
    "mem_faultins", "mem_recompute_drops", "mem_backpressure_events",
    "mem_backpressure_stall_s", "mem_violations", "mem_oom_events",
    "mem_checkpoints", "mem_checkpoint_blocks",
]
CHAOS_KEYS = [
    "chaos_transient_faults", "chaos_retries", "chaos_escalations",
    "chaos_backoff_s", "chaos_speculated", "chaos_spec_wins",
    "chaos_spec_cancelled", "chaos_nodes_failed", "chaos_blocks_lost",
    "chaos_blocks_replayed", "chaos_rerouted_ops", "chaos_oom_events",
    "chaos_oom_evicted", "chaos_makespan", "chaos_dead_nodes",
]


class TestGoldenSchema:
    def test_base_numpy_keys(self):
        ctx = make_ctx()
        X = ctx.random((64, 16), grid=(4, 1))
        (X.T @ X).compute()
        ctx.flush()
        expect = SUMMARY_KEYS + RUNTIME_KEYS + BACKEND_KEYS + MEM_KEYS
        assert list(ctx.loads().keys()) == expect

    def test_gc_budgeted_keys(self):
        # a per-node budget surfaces one extra cluster-summary key
        ctx = make_ctx(mem_capacity=1e5)
        X = ctx.random((64, 16), grid=(4, 1))
        (X.T @ X).compute()
        ctx.flush()
        expect = (SUMMARY_KEYS + ["mem_capacity_per_node"] + RUNTIME_KEYS
                  + BACKEND_KEYS + MEM_KEYS)
        assert list(ctx.loads().keys()) == expect

    def test_chaos_keys(self):
        ctx = make_ctx()
        ctx.enable_chaos(ChaosPlan(stragglers={1: 2.0}), seed=1)
        X = ctx.random((64, 16), grid=(4, 1))
        (X.T @ X).compute()
        ctx.flush()
        expect = (SUMMARY_KEYS + RUNTIME_KEYS + BACKEND_KEYS + MEM_KEYS
                  + CHAOS_KEYS)
        assert list(ctx.loads().keys()) == expect

    def test_linalg_sim_keys(self):
        # sim executor: no backend block; comm-bound keys follow runtime
        from repro.linalg import tsqr_indirect

        ctx = make_ctx(backend="sim")
        tsqr_indirect(ctx, ctx.random((4096, 64), grid=(4, 1)))
        comm = ["comm_moved_tsqr", "comm_lower_tsqr", "comm_ratio_tsqr"]
        expect = SUMMARY_KEYS + RUNTIME_KEYS + comm + MEM_KEYS
        assert list(ctx.loads().keys()) == expect

    def test_schema_matches_snapshot(self):
        ctx = make_ctx()
        X = ctx.random((64, 16), grid=(4, 1))
        (X.T @ X).compute()
        ctx.flush()
        assert ctx.metrics.schema() == list(ctx.loads().keys())
        assert ctx.metrics.provider_names() == [
            "cluster", "runtime", "comm", "backend", "memory", "chaos"]


# -- metrics registry unit behavior ------------------------------------------
class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("ops")
        g = reg.gauge("depth")
        h = reg.histogram("lat_s")
        c.inc()
        c.inc(2)
        g.set(7.5)
        for v in (0.001, 0.002, 0.003, 0.004):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["ops"] == 3
        assert snap["depth"] == 7.5
        assert snap["lat_s_count"] == 4
        assert snap["lat_s_sum"] == pytest.approx(0.010)
        # quantiles resolve to the bucket upper bound (Prometheus-style)
        assert 0.001 <= snap["lat_s_p50"] <= 0.01
        assert snap["lat_s_max"] == pytest.approx(0.004)

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            Counter("x").inc(-1)

    def test_duplicate_names_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")
        reg.register_provider("p", dict)
        with pytest.raises(ValueError):
            reg.register_provider("p", dict)

    def test_provider_order_is_registration_order(self):
        reg = MetricsRegistry()
        reg.register_provider("b", lambda: {"bb": 1})
        reg.register_provider("a", lambda: {"aa": 2})
        reg.counter("zz").inc()
        assert list(reg.snapshot().keys()) == ["bb", "aa", "zz"]

    def test_reset(self):
        reg = MetricsRegistry()
        c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
        c.inc(5)
        g.set(1.0)
        h.observe(0.5)
        reg.reset()
        snap = reg.snapshot()
        assert snap["c"] == 0 and snap["g"] == 0.0 and snap["h_count"] == 0

    def test_standalone_primitives(self):
        assert Counter("n").value == 0
        assert Gauge("v").value == 0.0
        assert Histogram("t").quantile(0.5) == 0.0


# -- trace invariants ---------------------------------------------------------
class TestTraceInvariants:
    def test_event_counts_match_dispatch_counters(self):
        ctx = make_ctx(trace=True)
        small_workload(ctx)
        c = dict(ctx.tracer.counts())
        s = ctx.executor.stats
        assert c["create"] == s.n_creates
        assert c["dispatch"] == s.n_rfc - s.n_creates
        assert c["retire"] == c["dispatch"]
        assert c["sched"] == c["dispatch"]
        # every dispatched op is placed on both simulated clock tracks
        assert c["op"] == 2 * c["dispatch"]

    def test_per_lane_timestamps_monotonic(self):
        ctx = make_ctx(trace=True)
        small_workload(ctx)
        lanes = {}
        for ev in ctx.tracer.of("op"):
            key = (ev.args["track"], ev.node, ev.worker)
            assert ev.t1 >= ev.t0
            assert ev.t0 >= lanes.get(key, 0.0) - 1e-12
            lanes[key] = ev.t0
        assert lanes  # the run produced op events

    def test_tracing_changes_no_bits_and_no_clocks(self):
        ref = make_ctx()
        b_ref = small_workload(ref)
        l_ref = ref.loads()
        ctx = make_ctx(trace=True)
        b = small_workload(ctx)
        loads = ctx.loads()
        assert b.tobytes() == b_ref.tobytes()
        assert loads["makespan_sync"] == l_ref["makespan_sync"]
        assert loads["makespan_pipelined"] == l_ref["makespan_pipelined"]
        assert list(loads.keys()) == list(l_ref.keys())

    def test_chaos_trace_deterministic_under_fixed_seed(self):
        def traced_run():
            ctx = make_ctx(k=4)
            ctx._install_tracer(FlightRecorder())
            plan = ChaosPlan(stragglers={1: 3.0}, transient_fault_prob=0.1,
                             link_degradation=1.5)
            ctx.enable_chaos(plan, seed=11)
            small_workload(ctx)
            # vertex ids are a process-global counter, so names like
            # "obj<vid>" shift between runs — renumber by first occurrence
            ids = {}
            return [(e.kind, ids.setdefault(e.name, len(ids)), e.node,
                     e.worker, e.t0, e.t1) for e in ctx.tracer.iter_events()]

        assert traced_run() == traced_run()

    def test_ring_buffer_bounds_and_drop_count(self):
        rec = FlightRecorder(capacity=16)
        for i in range(100):
            rec.record("op", f"e{i}")
        assert len(rec) == 16
        assert rec.dropped == 84
        # the ring keeps the newest events
        assert next(iter(rec.iter_events())).name == "e84"

    def test_reset_loads_clears_trace(self):
        ctx = make_ctx(trace=True)
        small_workload(ctx)
        assert len(ctx.tracer) > 0
        ctx.reset_loads()
        assert len(ctx.tracer) == 0

    def test_export_requires_tracing(self):
        ctx = make_ctx()
        with pytest.raises(RuntimeError):
            ctx.export_trace()

    def test_disabled_recorder_costs_nothing_structurally(self):
        # hot paths guard on `tracer is None`: an untraced context must not
        # hold a recorder anywhere
        ctx = make_ctx()
        assert ctx.tracer is None
        assert ctx.executor.tracer is None
        assert ctx.state.tracer is None
        assert ctx.state.clocks_sync.recorder is None
        assert ctx.state.clocks_pipe.recorder is None


# -- Perfetto export ----------------------------------------------------------
class TestPerfettoExport:
    def _trace(self):
        ctx = make_ctx(trace=True)
        small_workload(ctx)
        return ctx.export_trace()

    def test_document_structure(self, tmp_path):
        doc = self._trace()
        # JSON round-trip — what Perfetto's "Open trace file" will parse
        doc = json.loads(json.dumps(doc, default=float))
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert evs
        phases = {e["ph"] for e in evs}
        assert {"X", "M"} <= phases
        for e in evs:
            if e["ph"] == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0
                assert isinstance(e["pid"], int) and isinstance(e["tid"], int)

    def test_op_slices_per_lane(self):
        doc = self._trace()
        ops = [e for e in doc["traceEvents"]
               if e["ph"] == "X" and e.get("cat") == "op"]
        assert ops
        # primary track slices carry the binder decomposition the analyzer uses
        for e in ops:
            assert {"w_busy", "t_ready", "t_xfer", "out"} <= set(e["args"])

    def test_flow_arrows_pair_up(self):
        doc = self._trace()
        starts = [e for e in doc["traceEvents"] if e["ph"] == "s"]
        ends = [e for e in doc["traceEvents"] if e["ph"] == "f"]
        assert len(starts) == len(ends)
        assert {e["id"] for e in starts} == {e["id"] for e in ends}
        assert starts  # producer-retire -> consumer-start arrows exist

    def test_write_chrome_trace(self, tmp_path):
        ctx = make_ctx(trace=True)
        small_workload(ctx)
        path = tmp_path / "t.json"
        ctx.export_trace(str(path))
        doc = json.loads(path.read_text())
        assert doc["traceEvents"]
        assert doc["otherData"]["primary_track"] == "pipe"


# -- critical-path analysis ---------------------------------------------------
class TestCriticalPath:
    def test_decomposition_sums_to_makespan(self):
        ctx = make_ctx(trace=True)
        small_workload(ctx)
        a = analyze(ctx.export_trace())
        assert a["track"] == "pipe"
        assert abs(a["decomposition_total_pct"] - 100.0) <= 1.0
        assert all(v >= 0.0 for v in a["breakdown"].values())
        assert sum(a["breakdown"].values()) == pytest.approx(
            a["makespan"], rel=1e-9)

    def test_chaos_names_dominant_stall(self):
        # 1 dead node + stragglers + faults: the analyzer must attribute the
        # makespan and name *some* dominant non-compute cause deterministically
        from repro.launch.chaos import run_chaos_scenario

        report = run_chaos_scenario(nodes=4, iters=3, fail_nodes=1,
                                    stragglers=1, slowdown=4.0,
                                    fault_prob=0.05,
                                    check_determinism=False,
                                    trace_path=None)
        assert report["identical"]

        ctx = make_ctx(trace=True)
        plan = ChaosPlan(node_failures={3: 1e-7}, stragglers={1: 4.0},
                         transient_fault_prob=0.05)
        ctx.enable_chaos(plan, seed=3)
        small_workload(ctx)
        a = analyze(ctx.export_trace())
        assert a["track"] == "chaos"
        assert a["top_stall"] in ("transfer", "queue_stall", "retry",
                                  "eviction_stall", "none")
        assert abs(a["decomposition_total_pct"] - 100.0) <= 1.0

    def test_summary_line_and_segments(self):
        ctx = make_ctx(trace=True)
        small_workload(ctx)
        a = analyze(ctx.export_trace())
        line = summary_line(a)
        assert line.startswith("# trace:") and "critical path" in line
        segs = top_segments(a, n=3)
        assert 0 < len(segs) <= 3

    def test_trace_report_cli(self, tmp_path, capsys):
        from repro.launch.trace_report import main

        ctx = make_ctx(trace=True)
        small_workload(ctx)
        path = tmp_path / "t.json"
        ctx.export_trace(str(path))
        main([str(path)])
        out = capsys.readouterr().out
        assert "# trace:" in out
        assert "decomposition" in out
        assert "compute" in out


# -- pipelined drain accounting (SchedStats.drain_s) --------------------------
class TestDrainAccounting:
    def test_pipelined_drain_time_reported(self):
        ctx = make_ctx()
        small_workload(ctx)
        loads = ctx.loads()
        assert loads["drain_s"] > 0.0
        # drain is queue-drain wall time, kept out of the per-op dispatch
        # split so bench_overhead's scheduling-vs-dispatch numbers stay honest
        assert loads["drain_s"] == ctx.executor.stats.drain_s

    def test_sync_mode_has_no_drain(self):
        ctx = make_ctx(pipeline=False)
        X = ctx.random((64, 16), grid=(4, 1))
        (X.T @ X).compute()
        ctx.flush()
        assert ctx.loads()["drain_s"] == 0.0

    def test_nested_flush_counts_once(self):
        # revive/recover re-enter flush(); the re-entrancy depth counter must
        # charge the wall-clock window exactly once
        ctx = make_ctx()
        X = ctx.random((64, 16), grid=(4, 1))
        out = (X.T @ X).compute()
        ctx.executor.fail_node(2)
        ctx.executor.recover(
            [out.block(i).vid for i in out.grid.iter_indices()])
        ctx.flush()
        s = ctx.executor.stats
        assert s.drain_s >= 0.0
        assert ctx.executor._flush_depth == 0

    def test_trace_bitwise_with_gc_and_budget(self):
        ref = make_ctx(gc=True, mem_capacity=5e4)
        b_ref = small_workload(ref)
        ctx = make_ctx(gc=True, mem_capacity=5e4, trace=True)
        b = small_workload(ctx)
        assert b.tobytes() == b_ref.tobytes()
        kinds = set(dict(ctx.tracer.counts()))
        assert "dispatch" in kinds and "op" in kinds


# -- shared/explicit recorder -------------------------------------------------
class TestRecorderSharing:
    def test_context_accepts_recorder_instance(self):
        rec = FlightRecorder(capacity=1 << 12)
        ctx = make_ctx(trace=rec)
        assert ctx.tracer is rec
        small_workload(ctx)
        assert len(rec) > 0

    def test_capacity_int(self):
        ctx = make_ctx(trace=256)
        assert ctx.tracer.capacity == 256

    def test_export_includes_makespans(self):
        ctx = make_ctx(trace=True)
        small_workload(ctx)
        doc = export_chrome_trace(ctx.tracer, makespans={"pipe": 1.0})
        assert doc["otherData"]["makespans"] == {"pipe": 1.0}


def test_numpy_seed_unaffected_by_tracing():
    # the recorder must not touch any RNG: global numpy state advances
    # identically across a traced and untraced run
    np.random.seed(1234)
    ref = make_ctx()
    small_workload(ref)
    state_ref = np.random.get_state()[1].sum()
    np.random.seed(1234)
    ctx = make_ctx(trace=True)
    small_workload(ctx)
    assert np.random.get_state()[1].sum() == state_ref
