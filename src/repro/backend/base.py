"""``BlockBackend``: the compiled block-kernel execution protocol.

The scheduler decides *where* a block op runs (LSHS placements) and the
executor decides *when* (sync vs pipelined dispatch); a backend decides
*how*: which kernel implementation executes the block math and where block
values physically live between ops.  Placement decisions never depend on
block values, so every backend sees the identical schedule — backends are a
pure substitution of the execution substrate.

Contract:

* ``from_host(arr, placement)`` commits a host numpy array to backend
  storage (device_put for jax); ``to_host(value)`` converts back.  Both
  count in ``stats`` (``h2d``/``d2h``) — the executor's hot path must never
  call them between ops, which the host-transfer regression test asserts.
* ``execute(op, meta, inputs, placement)`` runs one block-level op on
  backend-resident inputs and returns a backend-resident output.
* ``run_program(program, inputs, placement)`` runs a ``Program`` — a
  segment of block ops — as one compiled call (compiled backends only;
  the pipelined executor lowers cached plans through it).
* ``compile_cache`` is the backend's structural compile cache (``None`` for
  interpreters with nothing to compile).

Backends must be bit-exact replaceable at equal precision: the ``numpy``
backend is the reference semantics (``graph_array.execute_block_op``), and
jax/pallas must match it within dtype-appropriate tolerance on every op.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from .compile_cache import CompileCache, program_key


@dataclass
class BackendStats:
    """Execution-substrate counters (complement ``ExecStats``, which counts
    dispatches, and ``SchedStats``, which counts scheduling time)."""

    dispatches: int = 0     # host calls of compiled executables (an op or a program)
    dispatch_s: float = 0.0  # host time issuing cached compiled ops (nums:dispatch)
    jit_calls: int = 0      # compiled-callable invocations (jax/pallas)
    h2d: int = 0            # host -> device commits (from_host)
    d2h: int = 0            # device -> host gathers (to_host)
    sync_s: float = 0.0     # host time blocked on the device (nums:sync)
    device_moves: int = 0   # device -> device operand moves
    device_move_bytes: int = 0  # bytes of those operands (nums:move)
    fallbacks: int = 0      # ops run on the host: 0, a missing lowering raises
    replays: int = 0        # lineage-replay re-executions (fault recovery)
    programs: int = 0       # run_program() calls: one lowered segment each
    lowered_ops: int = 0    # block ops run inside those programs
    gram_ops: int = 0       # weighted-Gram (wgram) block ops run, per op or in programs

    def reset(self) -> None:
        self.dispatches = 0
        self.dispatch_s = 0.0
        self.jit_calls = 0
        self.h2d = 0
        self.d2h = 0
        self.sync_s = 0.0
        self.device_moves = 0
        self.device_move_bytes = 0
        self.fallbacks = 0
        self.replays = 0
        self.programs = 0
        self.lowered_ops = 0
        self.gram_ops = 0

    def as_dict(self) -> Dict[str, float]:
        return {
            "backend_dispatches": self.dispatches,
            "backend_dispatch_s": self.dispatch_s,
            "backend_jit_calls": self.jit_calls,
            "backend_h2d": self.h2d,
            "backend_d2h": self.d2h,
            "backend_sync_s": self.sync_s,
            "backend_device_moves": self.device_moves,
            "backend_device_move_bytes": self.device_move_bytes,
            "backend_fallbacks": self.fallbacks,
            "backend_replays": self.replays,
            "backend_programs": self.programs,
            "backend_lowered_ops": self.lowered_ops,
            "backend_gram_ops": self.gram_ops,
        }


class Program:
    """A segment of block ops that runs as one compiled call.

    ``ops`` is a tuple of ``(op, meta, args)`` in a topological order; an
    arg ``j >= 0`` is the result of the j-th op, ``~i`` the i-th input.
    ``outputs`` lists the ops whose results the program returns, in order.
    ``key`` is structural (op kinds, canonical metadata, wiring, outputs),
    so structurally identical segments share one compilation.
    ``gram_ops`` counts its weighted-Gram ops (``BackendStats.gram_ops``)."""

    __slots__ = ("ops", "outputs", "key", "gram_ops")

    def __init__(self, ops: Tuple[Tuple[str, Dict[str, Any], Tuple[int, ...]], ...],
                 outputs: Tuple[int, ...]):
        self.ops = ops
        self.outputs = outputs
        self.key = program_key(ops, outputs)
        self.gram_ops = sum(1 for op, _meta, _args in ops if op == "wgram")


class BlockBackend:
    """Abstract block-kernel execution backend (see module docstring)."""

    name: str = "abstract"

    def __init__(self, dtype: str = "float64"):
        self.dtype = dtype
        self.stats = BackendStats()
        # flight recorder (core.trace): when set, compiled backends record
        # compile-cache hits/misses at dispatch time
        self.tracer = None

    # -- storage ------------------------------------------------------------
    def from_host(self, arr: np.ndarray, placement: Tuple[int, int]):
        raise NotImplementedError

    def to_host(self, value) -> np.ndarray:
        raise NotImplementedError

    # -- execution ----------------------------------------------------------
    def execute(self, op: str, meta: Dict[str, Any], inputs: Sequence[Any],
                placement: Tuple[int, int]):
        raise NotImplementedError

    def run_program(self, program: Program, inputs: Sequence[Any],
                    placement: Tuple[int, int]) -> Tuple[Any, ...]:
        """Run ``program`` on backend-resident ``inputs`` at ``placement``;
        return the results of ``program.outputs``."""
        raise NotImplementedError

    def wait(self, value) -> None:
        """Block until ``value`` is ready (no-op for synchronous backends;
        async runtimes override — the readiness barrier behind
        ``GraphArray.wait``)."""

    # -- spill channel -------------------------------------------------------
    # Memory-budgeted eviction moves block values to a host-side store and
    # back through the same from_host/to_host paths (counted as d2h/h2d so
    # the host-transfer regression test keeps seeing the hot path clean).
    def spill_out(self, value) -> np.ndarray:
        """Evict a backend-resident block value to a host numpy array."""
        return self.to_host(value)

    def spill_in(self, host: np.ndarray, placement: Tuple[int, int]):
        """Fault a spilled host array back into backend storage."""
        return self.from_host(host, placement)

    # -- introspection -------------------------------------------------------
    @property
    def compile_cache(self) -> Optional[CompileCache]:
        return None

    def counters(self) -> Dict[str, float]:
        d: Dict[str, float] = dict(self.stats.as_dict())
        cc = self.compile_cache
        if cc is not None:
            d.update(cc.counters())
        return d
