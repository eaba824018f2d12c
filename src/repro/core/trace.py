"""Flight recorder: a bounded in-memory event log for the block runtime.

Opt-in via ``ArrayContext(trace=True)`` (or ``--trace out.json`` on the
launch drivers).  When enabled, every runtime boundary appends one
``TraceEvent`` to a ring buffer:

==============  ==========================================================
kind            emitted at
==============  ==========================================================
``create``      ``Executor.create`` — block materialized from a creation op
``dispatch``    ``Executor.run_op`` — op handed to the executor (any mode)
``sched``       ``SchedulerBase._dispatch`` — placement decision made
``op``          ``WorkerClocks.place`` — simulated (start, finish) on one
                clock track (``args["track"]`` is ``sync`` / ``pipe`` /
                ``chaos``), with the start-time breakdown (worker-busy,
                operand-ready, transfer-arrival) the critical-path analyzer
                attributes stalls from
``retire``      ``Executor._execute`` — block value materialized (wall time)
``transfer``    ``ClusterState.transition`` — one operand move with element
                and byte counts (``intra`` marks worker->worker moves)
``backpressure``/``mem_stall``  memory-watermark stall charged to a lane
``evict_spill``/``evict_drop``  eviction victim spilled to host / dropped
``fault_in``    spilled block reloaded over h2d
``gc_free``     refcount GC freed a dead block
``oom``         injected OOM shrank a node budget (chaos)
``retry``       transient-fault retries + backoff charged before an op
``spec_win``/``spec_loss``      speculative duplicate won / was cancelled
``reroute``     op moved off a dead node
``node_death``  node killed mid-drain (``args["lost"]`` blocks dropped)
``replay``      lineage replay re-executed a lost block
``plan_hit``/``plan_miss``      plan-cache lookup outcome
``compile_hit``/``compile_miss``  structural kernel cache
==============  ==========================================================

Times ``t0``/``t1`` are *simulated* seconds on the event's clock track
(0 when the event has no simulated extent); ``wall`` is host
``perf_counter`` seconds relative to the recorder's epoch.  The buffer is a
``collections.deque(maxlen=capacity)``: when full, the oldest event is
dropped and ``dropped`` increments, so tracing never grows unbounded.
Disabled tracing costs one attribute load + ``is None`` test per boundary.

Overhead discipline: the buffer holds *raw tuples*; :class:`TraceEvent`
objects (and the hot ``op`` event's args dict, including the
binding-operand argmax) are materialized lazily at read time
(``iter_events``/``of``/export), so the recording path is one tuple build +
one deque append.

Viewing a trace in Perfetto
---------------------------
Export with ``ctx.export_trace("out.json")`` (or pass ``--trace out.json``
to ``repro.launch.blocks`` / ``repro.launch.chaos``).  The file is Chrome
``trace_event`` JSON: open https://ui.perfetto.dev and use
"Open trace file" (or navigate to ``chrome://tracing`` in Chrome and click
"Load").  Each simulated node renders as a process row, each worker as a
thread lane; flow arrows connect a producer's retirement to its consumers'
starts; instant markers flag retries, evictions, GC frees, OOMs and node
deaths.  1 simulated second = 1e6 display units (``ts`` is microseconds).

Summarize from the shell with::

    python -m repro.launch.trace_report out.json

which prints the critical path and the makespan decomposition
(compute / transfer / queue-stall / retry / eviction-stall per node).

Host spans on the device's clock
--------------------------------
The flight recorder's clocks are simulated, and its ``wall`` runs from its
own epoch: neither lines up with a device trace.  The block runtime's host
work is therefore also marked with :class:`Span`, always on.  Each span
emits a ``jax.profiler.TraceAnnotation`` under one of a fixed set of names
and adds its wall time to a counter of its layer's stats object, which
``ArrayContext.loads()`` reports:

=====================  ====================================  ===========================
span                   region                                ``loads()`` counter
=====================  ====================================  ===========================
``nums:compute``       ``ArrayContext.compute``              (parent of the next three)
``nums:fingerprint``   structural fingerprint of a graph     ``fingerprint_s`` (in
                                                             ``sched_overhead_s``)
``nums:replay``        replay of a cached plan               ``replay_s`` (same)
``nums:schedule``      cold LSHS placement                   ``sched_cold_s`` (same)
``nums:drain``         outermost ``Executor.flush``          ``drain_s``
``nums:dispatch``      one compiled block op or lowered      ``backend_dispatch_s``
                       program issued (``JaxBackend._call``,
                       hit)
``nums:compile``       compile and first run of a block op   the compile cache's
                       or program (``JaxBackend._call``,     ``compile_s``
                       miss)
``nums:sync``          host blocked on the device            ``backend_sync_s``
                       (``JaxBackend.wait``/``to_host``)
``nums:move``          one cross-chip ``device_put``         ``backend_device_move_bytes``
                       (``JaxBackend._colocate``)            (operand ``nbytes``)
``nums:pygc``          one CPython cyclic collection         ``pygc_s``, ``pygc_gen2``
                       (``gc.callbacks``, process-wide)      (full collections)
``nums:newton.iter``   one ``NewtonSolver.fit`` iteration    none
``nums:reshard``       one ``reshard`` or ``reshard_naive``  ``reshard_s``
                       call (``core/reshard.py``)
``nums:cpals.layout``  a CP-ALS fit's three mode layouts     none
                       and unfoldings (``factor/cpals.py``)
``nums:cpals.sweep``   one CP-ALS sweep over the three       none
                       modes
=====================  ====================================  ===========================

One counter has no span: ``layout_bytes`` in ``loads()`` adds the output
bytes of every ``slice``, ``concat_blocks`` and ``matricize`` block op as
it runs (``Executor._execute``), on the per-op path and inside lowered
programs alike.

They nest: ``fingerprint``, ``replay`` and ``schedule`` lie in
``compute``; ``dispatch``, ``compile`` and ``move`` lie in ``drain`` (in
``compute`` itself for a synchronous executor); ``pygc`` can fall inside any
span.  A ``reshard`` holds the ``compute`` calls that schedule its source
and its move graph, and lies in ``cpals.layout`` or ``cpals.sweep`` when
CP-ALS calls it; ``cpals.layout`` and each ``cpals.sweep`` hold the
``compute`` calls of their block ops.  A pipelined executor drains when a
value is read, so in a CP-ALS fit ``drain`` lies outside ``cpals.layout``
and ``cpals.sweep`` (the factors are read after ``cp_als`` returns); a
synchronous executor runs each op inside its ``compute``.  Capture them
with the JAX profiler around any program::

    with jax.profiler.trace("prof"):
        GLM(ctx).fit(X, y)

and open the ``.xplane.pb`` under ``prof/plugins/profile/`` in
TensorBoard's profile plugin, or in https://ui.perfetto.dev (pass
``create_perfetto_trace=True`` for a ``perfetto_trace.json.gz``).  The spans
sit in the host plane of the same file as the device's ops, on the same
clock.  With no profiler session running a span costs one check beyond its
two clock reads; where jax cannot be imported it is its timer only.
"""
from __future__ import annotations

import gc
from collections import deque
from time import perf_counter
from typing import Any, Callable, Dict, Iterable, List, Optional

DEFAULT_CAPACITY = 1 << 17  # 131072 events; smoke-scale runs use ~1e4

# the fixed set of host span names (module docstring)
SPAN_COMPUTE = "nums:compute"
SPAN_FINGERPRINT = "nums:fingerprint"
SPAN_REPLAY = "nums:replay"
SPAN_SCHEDULE = "nums:schedule"
SPAN_DRAIN = "nums:drain"
SPAN_DISPATCH = "nums:dispatch"
SPAN_COMPILE = "nums:compile"
SPAN_SYNC = "nums:sync"
SPAN_MOVE = "nums:move"
SPAN_PYGC = "nums:pygc"
SPAN_NEWTON_ITER = "nums:newton.iter"
SPAN_RESHARD = "nums:reshard"
SPAN_CPALS_LAYOUT = "nums:cpals.layout"
SPAN_CPALS_SWEEP = "nums:cpals.sweep"


class _NoProfiler:
    """Stands in for ``TraceAnnotation`` where jax cannot be imported."""

    @staticmethod
    def is_enabled() -> bool:
        return False


_ANNOTATION: Any = None  # jax.profiler.TraceAnnotation, resolved on first use


def _annotation_class() -> Any:
    global _ANNOTATION
    try:
        from jax.profiler import TraceAnnotation as cls
    except ImportError:
        cls = _NoProfiler
    _ANNOTATION = cls
    return cls


class Span:
    """One host region: a profiler annotation named ``name`` while a profiler
    session runs, and its wall seconds in ``elapsed`` on exit, added to
    ``stats.<field>`` when a field is given.

    ``with Span(SPAN_DRAIN, self.stats, "drain_s"): ...``
    """

    __slots__ = ("name", "stats", "field", "elapsed", "_t0", "_ann")

    def __init__(self, name: str, stats: Any = None, field: Optional[str] = None):
        self.name = name
        self.stats = stats
        self.field = field
        self.elapsed = 0.0

    # The annotation stamps its start when it is made and its end on exit,
    # so the timer's reads sit right after each stamp: the span's duration
    # in the trace and in its counter differ by two clock reads.
    def __enter__(self) -> "Span":
        cls = _ANNOTATION or _annotation_class()
        if cls.is_enabled():
            self._ann = ann = cls(self.name)
            self._t0 = perf_counter()
            ann.__enter__()
        else:
            self._ann = None
            self._t0 = perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self.elapsed = perf_counter() - self._t0
        if self.field is not None:
            setattr(self.stats, self.field,
                    getattr(self.stats, self.field) + self.elapsed)


class GcSpans:
    """CPython's cyclic collector as ``nums:pygc`` spans, through
    ``gc.callbacks``: the wall seconds of every collection in ``seconds``,
    full (generation 2) collections counted in ``full``.  One instance per
    process (``PYGC``); a collection is annotated only once a :class:`Span`
    has resolved the profiler (no import runs inside the collector)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.full = 0
        self._clock = perf_counter  # still bound while modules are torn down
        self._t0 = 0.0
        self._ann = None

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            cls = _ANNOTATION
            if cls is not None and cls.is_enabled():
                self._ann = cls(SPAN_PYGC)
                self._t0 = self._clock()
                self._ann.__enter__()
            else:
                self._t0 = self._clock()
            return
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.seconds += self._clock() - self._t0
        if info["generation"] == 2:
            self.full += 1


PYGC = GcSpans()


def install_gc_spans() -> GcSpans:
    """Hook :data:`PYGC` into ``gc.callbacks``; a second call adds nothing."""
    if not any(cb is PYGC for cb in gc.callbacks):
        gc.callbacks.append(PYGC)
    return PYGC


class TraceEvent:
    """One structured runtime event (see module docstring for kinds)."""

    __slots__ = ("kind", "name", "node", "worker", "t0", "t1", "wall", "args")

    def __init__(self, kind: str, name: str, node: int, worker: int,
                 t0: float, t1: float, wall: float, args: Dict[str, Any]):
        self.kind = kind
        self.name = name
        self.node = node
        self.worker = worker
        self.t0 = t0
        self.t1 = t1
        self.wall = wall
        self.args = args

    def as_dict(self) -> Dict[str, Any]:
        return {
            "kind": self.kind, "name": self.name, "node": self.node,
            "worker": self.worker, "t0": self.t0, "t1": self.t1,
            "wall": self.wall, "args": self.args,
        }

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"TraceEvent({self.kind}, {self.name!r}, n{self.node}w"
                f"{self.worker}, t0={self.t0:.3g}, t1={self.t1:.3g})")


class FlightRecorder:
    """Bounded ring buffer of :class:`TraceEvent`.

    Instrumented call sites hold a ``tracer``/``recorder`` attribute that is
    ``None`` when tracing is off; the recorder itself never mutates runtime
    state (clocks, RNG, stores), so tracing is bit- and clock-neutral by
    construction (``tests/test_obs.py``).
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity <= 0:
            raise ValueError(f"trace capacity must be positive: {capacity}")
        self.capacity = int(capacity)
        self.events: deque = deque(maxlen=self.capacity)
        self.dropped = 0
        self._epoch = perf_counter()

    # -- hot path ---------------------------------------------------------
    def record(self, kind: str, name: str = "", node: int = -1,
               worker: int = -1, t0: float = 0.0, t1: float = 0.0,
               args: Optional[Dict[str, Any]] = None) -> None:
        ev = self.events
        if len(ev) == self.capacity:
            self.dropped += 1
        ev.append((kind, name, node, worker, t0, t1,
                   perf_counter() - self._epoch, args))

    # -- clock-track taps -------------------------------------------------
    def attach_clocks(self, clocks, track: str) -> None:
        """Install a per-``place`` tap on one ``WorkerClocks`` track: every
        simulated op placement becomes an ``op`` event tagged ``track``."""
        clocks.recorder = self._clock_recorder(track)

    def _clock_recorder(self, track: str) -> Callable:
        # the hottest record site (2-3 op events per dispatched op): one raw
        # tuple append, nothing else.  The args dict — including the
        # binding-operand argmax — is built lazily in _materialize.
        # ``in_objs``/``xlog`` are fresh lists per ``place`` call and never
        # mutated afterwards, so holding references is safe; ``clocks.ready``
        # entries are write-once per object (chaos replays may overwrite, in
        # which case lazy materialization sees the final — still
        # deterministic — value).
        events, epoch = self.events, self._epoch

        def rec(clocks, node, worker, out_obj, work, in_objs, xlog,
                w_busy, t_ready, t_xfer, start, end):
            if len(events) == self.capacity:
                self.dropped += 1
            events.append(("op", track, node, worker, start, end,
                           perf_counter() - epoch,
                           (clocks, out_obj, work, in_objs, xlog,
                            w_busy, t_ready, t_xfer)))
        return rec

    @staticmethod
    def _materialize(raw) -> TraceEvent:
        kind, name, node, worker, t0, t1, wall, args = raw
        if type(args) is tuple:  # deferred payload (hot sites skip the dict)
            if kind == "op":
                (clocks, out_obj, work, in_objs, xlog,
                 w_busy, t_ready, t_xfer) = args
                # binding operand: the input whose availability set t_ready
                # (first max wins — deterministic)
                ready_obj, best = -1, -1.0
                ready = clocks.ready
                for obj, _e in in_objs:
                    t = ready.get(obj, 0.0)
                    if t > best:
                        best, ready_obj = t, obj
                args = {
                    "track": name, "out": out_obj,
                    "ins": [obj for obj, _e in in_objs],
                    "w_busy": w_busy, "t_ready": t_ready, "t_xfer": t_xfer,
                    "ready_obj": ready_obj, "work": work, "xfers": xlog,
                }
            elif kind == "dispatch":
                out_id, in_ids, queued = args
                args = {"out": out_id, "ins": in_ids, "queued": queued}
            elif kind == "sched":
                args = {"out": args[0], "options": args[1]}
        elif args is None:
            args = {}
        return TraceEvent(kind, name, node, worker, float(t0), float(t1),
                          wall, args)

    def on_transition(self, state, node: int, worker: int, out_obj: int,
                      out_elements: int, new_transfers,
                      eta_sync, eta_pipe) -> None:
        """``ClusterState.transition`` tap: record the operand moves this
        transition caused, with byte counts from the cost model."""
        bpe = state.cost_model.bytes_per_element
        for tr in new_transfers:
            self.record("transfer", f"obj{tr.obj}", tr.dst, worker, args={
                "obj": tr.obj, "src": tr.src, "dst": tr.dst,
                "elements": int(tr.elements),
                "bytes": int(tr.elements * bpe),
                "intra": bool(tr.intra_node),
            })

    # -- inspection -------------------------------------------------------
    def __len__(self) -> int:
        return len(self.events)

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for raw in self.events:
            out[raw[0]] = out.get(raw[0], 0) + 1
        return out

    def of(self, *kinds: str) -> List[TraceEvent]:
        want = set(kinds)
        return [self._materialize(raw) for raw in self.events
                if raw[0] in want]

    def iter_events(self) -> Iterable[TraceEvent]:
        return (self._materialize(raw) for raw in self.events)

    def clear(self) -> None:
        self.events.clear()
        self.dropped = 0
        self._epoch = perf_counter()
