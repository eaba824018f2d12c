"""Host spans of the block runtime (``core/trace.py`` ``Span``): each one is a
``nums:`` annotation in the JAX profiler's host plane, on the device trace's
clock, and a counter in ``ArrayContext.loads()``; the two agree, spans nest
as documented, and they change no bits."""
import gc
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import ArrayContext, ClusterSpec
from repro.core import trace as trace_mod
from repro.core.trace import PYGC, SPAN_DRAIN, Span, install_gc_spans
from repro.glm import GLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# span -> the counter its durations are added to (``loads()``, and
# ``SchedStats`` for the parts of ``sched_overhead_s``)
COUNTED = {
    "nums:fingerprint": "fingerprint_s",
    "nums:replay": "replay_s",
    "nums:drain": "drain_s",
    "nums:dispatch": "backend_dispatch_s",
    "nums:sync": "backend_sync_s",
}
# child span -> the span each of its intervals lies in
PARENT = {
    "nums:fingerprint": "nums:compute",
    "nums:replay": "nums:compute",
    "nums:schedule": "nums:compute",
    "nums:dispatch": "nums:drain",
    "nums:compute": "nums:newton.iter",
}


def _data(n=4_000, d=6, seed=3):
    rng = np.random.default_rng(seed)
    y = (rng.random(n) < 0.5).astype(np.float64)
    X = rng.standard_normal((n, d)) + 0.5 * (y[:, None] - 0.5)
    return X, y.reshape(-1, 1)


def _ctx():
    return ArrayContext(cluster=ClusterSpec(1, 4), node_grid=(1,), backend="jax",
                        pipeline=True, plan_cache=True, gc=True)


def _fit(ctx, X, y):
    model = GLM(ctx, max_iter=4, tol=1e-9, reg=1e-6)
    model.fit(X, y)
    return model.beta


def _counters(ctx):
    return {**ctx.sched_stats.as_dict(), **ctx.loads()}


def _host_spans(log_dir):
    """``{name: [(start_ns, end_ns), ...]}`` of every ``nums:`` event in the
    trace's host planes."""
    import glob

    import jax

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("nums:"):
                    out.setdefault(ev.name, []).append((ev.start_ns, ev.end_ns))
    return out


@pytest.fixture(scope="module")
def traced_fit(tmp_path_factory):
    """A warm Newton fit under ``jax.profiler.trace``: its spans and the
    growth of ``loads()`` over it."""
    import jax

    X, y = _data()
    ctx = _ctx()
    Xg, yg = ctx.from_numpy(X, grid=(4, 1)), ctx.from_numpy(y, grid=(4, 1))
    _fit(ctx, Xg, yg)  # compiles and fills the plan cache
    log_dir = str(tmp_path_factory.mktemp("prof"))
    before = _counters(ctx)
    with jax.profiler.trace(log_dir):
        _fit(ctx, Xg, yg)
    after = _counters(ctx)
    growth = {k: after[k] - before[k] for k in COUNTED.values()}
    return _host_spans(log_dir), growth


def test_profiler_trace_holds_the_runtime_spans(traced_fit):
    spans, _ = traced_fit
    for name in ("nums:compute", "nums:fingerprint", "nums:replay",
                 "nums:drain", "nums:dispatch", "nums:sync", "nums:newton.iter"):
        assert spans.get(name), (name, sorted(spans))
    assert len(spans["nums:newton.iter"]) == 4
    # warm: every executable and every plan was cached by the first fit
    assert "nums:compile" not in spans and "nums:schedule" not in spans


@pytest.mark.parametrize("child", sorted(PARENT))
def test_each_child_span_lies_in_its_parent(traced_fit, child):
    spans, _ = traced_fit
    parents = spans[PARENT[child]]
    for s, e in spans.get(child, ()):
        assert any(ps <= s and e <= pe for ps, pe in parents), (child, s, e)


@pytest.mark.parametrize("name", sorted(COUNTED))
def test_trace_durations_match_the_counters(traced_fit, name):
    spans, growth = traced_fit
    traced_s = sum(e - s for s, e in spans[name]) / 1e9
    counted_s = growth[COUNTED[name]]
    assert counted_s > 0
    assert abs(traced_s - counted_s) <= 0.1 * counted_s, (traced_s, counted_s)


def test_spans_change_no_bits(tmp_path):
    import jax

    X, y = _data(seed=5)

    def fit():
        ctx = _ctx()
        return _fit(ctx, ctx.from_numpy(X, grid=(4, 1)), ctx.from_numpy(y, grid=(4, 1)))

    plain = fit()
    with jax.profiler.trace(str(tmp_path)):
        traced = fit()
    assert traced.tobytes() == plain.tobytes()


def test_full_collections_are_counted_once():
    install_gc_spans()
    install_gc_spans()
    assert sum(cb is PYGC for cb in gc.callbacks) == 1
    ctx = ArrayContext(cluster=ClusterSpec(1, 2))
    was_enabled = gc.isenabled()
    gc.disable()  # only the collections below run in the window
    try:
        before = ctx.loads()
        for _ in range(3):
            gc.collect(2)
        after = ctx.loads()
    finally:
        if was_enabled:
            gc.enable()
    assert after["pygc_gen2"] - before["pygc_gen2"] == 3
    assert after["pygc_s"] > before["pygc_s"]


def test_span_without_jax_is_its_timer(monkeypatch):
    monkeypatch.setattr(trace_mod, "_ANNOTATION", trace_mod._NoProfiler)

    class Stats:
        drain_s = 0.25

    st = Stats()
    with Span(SPAN_DRAIN, st, "drain_s") as sp:
        sum(range(1000))
    assert sp.elapsed > 0
    assert st.drain_s == 0.25 + sp.elapsed


def test_move_bytes_are_the_moved_operands_nbytes():
    """On four virtual CPU devices, ``backend_device_move_bytes`` is the sum
    of the ``nbytes`` of every operand ``_colocate`` put on another device."""
    code = f"""
        import sys
        sys.path.insert(0, {os.path.join(REPO, "src")!r})
        import jax
        import numpy as np
        from repro.core import ArrayContext, ClusterSpec
        from repro.glm import GLM

        moved = []
        put = jax.device_put

        def counting_put(x, device=None, *args, **kwargs):
            if isinstance(x, jax.Array):  # device to device, not from_host
                moved.append(x.nbytes)
            return put(x, device, *args, **kwargs)

        jax.device_put = counting_put
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(0)
        X = rng.standard_normal((8_000, 5))
        y = (rng.random((8_000, 1)) < 0.5).astype(np.float64)
        ctx = ArrayContext(cluster=ClusterSpec(4, 2), node_grid=(4, 1),
                           backend="jax", pipeline=True, plan_cache=True, gc=True)
        Xg, yg = ctx.from_numpy(X, grid=(8, 1)), ctx.from_numpy(y, grid=(8, 1))
        GLM(ctx, max_iter=3, tol=1e-9, reg=1e-6).fit(Xg, yg).beta
        loads = ctx.loads()
        print("MOVES", loads["backend_device_moves"], len(moved))
        print("BYTES", loads["backend_device_move_bytes"], sum(moved))
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in r.stdout.splitlines())
    moves, n_moved = map(int, lines["MOVES"].split())
    counted, summed = map(int, lines["BYTES"].split())
    assert moves == n_moved > 0
    assert counted == summed > 0


@pytest.fixture(scope="module")
def traced_cpals(tmp_path_factory):
    """A warm CP-ALS fit of two sweeps, factors read, under
    ``jax.profiler.trace``: its spans and the growth of ``reshard_s``."""
    import jax

    from repro.factor import cp_als

    rng = np.random.default_rng(8)
    ctx = ArrayContext(cluster=ClusterSpec(1, 8), node_grid=(1, 1, 1),
                       backend="jax", pipeline=True, plan_cache=True, gc=True)
    X = ctx.from_numpy(rng.standard_normal((16, 16, 16)), grid=(8, 1, 1))

    def fit():
        res = cp_als(X, rank=3, iters=2, seed=1, track_fit=False)
        return [f.to_numpy() for f in res.factors]

    fit()  # compiles and fills the plan cache
    log_dir = str(tmp_path_factory.mktemp("prof_cpals"))
    before = ctx.loads()["reshard_s"]
    with jax.profiler.trace(log_dir):
        fit()
    return _host_spans(log_dir), ctx.loads()["reshard_s"] - before


def _inside(span, parents):
    s, e = span
    return any(ps <= s and e <= pe for ps, pe in parents)


def test_cpals_spans_nest_as_documented(traced_cpals):
    spans, _ = traced_cpals
    layout, sweeps = spans["nums:cpals.layout"], spans["nums:cpals.sweep"]
    assert len(layout) == 1 and len(sweeps) == 2
    # two reshards of the tensor, then one factor gather per mode and sweep
    assert len(spans["nums:reshard"]) == 2 + 3 * 2
    cpals = layout + sweeps
    assert all(_inside(r, cpals) for r in spans["nums:reshard"])
    assert all(_inside(c, cpals) for c in spans["nums:compute"])
    # the pipelined executor drains when the factors are read
    assert spans["nums:drain"]
    assert not any(_inside(d, cpals) for d in spans["nums:drain"])


def test_reshard_spans_match_reshard_s(traced_cpals):
    spans, counted_s = traced_cpals
    traced_s = sum(e - s for s, e in spans["nums:reshard"]) / 1e9
    assert counted_s > 0
    assert abs(traced_s - counted_s) <= 0.1 * counted_s, (traced_s, counted_s)
