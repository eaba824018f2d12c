"""The jax backend: per-op ``jax.jit`` with device-resident block storage.

Blocks stay ``jax.Array``s end-to-end: ``from_host`` commits a host block to
its placement's device once at creation, every block op executes as a
compiled XLA callable over device-resident operands, and values only return
to the host at ``assemble``/``to_numpy`` time.  There is no per-op
device->host->numpy->``device_put`` round-trip — the regression test counts
``stats.h2d``/``stats.d2h`` across op execution to pin this down.

Compilations are memoized in the structural compile cache
(``compile_cache.GLOBAL_COMPILE_CACHE``): key = op kind + interned canonical
metadata + input (shape, dtype) signature, so an iterative workload compiles
each distinct block kernel once and dispatches cached executables ever
after.  ``fused`` vertex chains lower through ``graph_array.apply_chain``
with jnp op tables *inside* one traced function, so a chain of n elementwise
ops is a single XLA fusion and a single dispatch (vs n interpreter steps).

Placements map node -> ``jax.Device`` (node i -> ``devices[i % len]``); on a
single-device host every node shares device 0 and operand moves are no-ops.

dtype: jax defaults to float32; requesting ``float64`` enables jax's
process-global x64 mode (``jax.config.update("jax_enable_x64", True)``) so
the backend can be bit-comparable to the numpy reference — see
``ArrayContext``'s dtype documentation for the trade-off.

Precision: a block op with a float32 operand is traced under
``jax.default_matmul_precision("highest")``, on the per-op path and inside
segment programs alike, so its contractions compute in float32 and not in
XLA's TPU default of one bfloat16 pass.  float64 ops keep their lowering.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.graph_array import apply_chain
from repro.core.trace import (
    SPAN_COMPILE,
    SPAN_DISPATCH,
    SPAN_MOVE,
    SPAN_SYNC,
    Span,
)

from .base import BlockBackend, Program
from .compile_cache import GLOBAL_COMPILE_CACHE, CompileCache, structural_key


def _jnp_tables(jnp):
    """jnp mirrors of ``graph_array._UNARY`` / ``_BINARY`` (same formulas, so
    f64 results agree with numpy to rounding of the same order), except
    sigmoid: on a TPU v5e the float32 ``log1p`` inside ``logaddexp`` is off by
    up to 2.6e-4 relative, which biased Newton's logistic-regression
    coefficients by 2.3e-6 at the HIGGS shape; ``lax.logistic`` is not."""
    import jax

    unary = {
        "neg": lambda x: -x,
        "exp": jnp.exp,
        "log": jnp.log,
        "sqrt": jnp.sqrt,
        "abs": jnp.abs,
        "square": jnp.square,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "identity": lambda x: x,
        "softplus": lambda x: jnp.logaddexp(0.0, x),
        "relu": lambda x: jnp.maximum(x, 0.0),
        "rsqrt": lambda x: 1.0 / jnp.sqrt(x),
        "reciprocal": lambda x: 1.0 / x,
    }
    binary = {
        "add": jnp.add,
        "sub": jnp.subtract,
        "mul": jnp.multiply,
        "div": jnp.divide,
        "pow": jnp.power,
        "maximum": jnp.maximum,
        "minimum": jnp.minimum,
    }
    return unary, binary


class JaxBackend(BlockBackend):
    name = "jax"
    _salt = "jax"  # compile-cache flavor for this backend's lowerings

    def __init__(self, dtype: str = "float32", devices: Optional[list] = None,
                 cache: Optional[CompileCache] = None):
        super().__init__(dtype)
        import jax
        import jax.numpy as jnp

        if dtype == "float64" and not jax.config.jax_enable_x64:
            # process-global: f64 blocks require x64 mode (weak-typed f32
            # kernels elsewhere in the process are unaffected)
            jax.config.update("jax_enable_x64", True)
        self._jax = jax
        self._jnp = jnp
        self._devices = list(devices) if devices else jax.devices()
        self._unary, self._binary = _jnp_tables(jnp)
        self._cache = cache if cache is not None else GLOBAL_COMPILE_CACHE

    # -- storage ------------------------------------------------------------
    def device_of(self, placement: Tuple[int, int]):
        return self._devices[placement[0] % len(self._devices)]

    def from_host(self, arr: np.ndarray, placement: Tuple[int, int]):
        self.stats.h2d += 1
        arr = np.asarray(arr, dtype=self.dtype)
        return self._jax.device_put(arr, self.device_of(placement))

    def to_host(self, value) -> np.ndarray:
        self.stats.d2h += 1
        with Span(SPAN_SYNC, self.stats, "sync_s"):
            return np.asarray(value)

    def wait(self, value) -> None:
        with Span(SPAN_SYNC, self.stats, "sync_s"):
            self._jax.block_until_ready(value)

    # -- execution ----------------------------------------------------------
    @property
    def device_count(self) -> int:
        """Devices that placements map onto (node ``i`` -> device
        ``i % device_count``, ``device_of``)."""
        return len(self._devices)

    def execute(self, op: str, meta: Dict[str, Any], inputs: Sequence[Any],
                placement: Tuple[int, int]):
        self.stats.dispatches += 1
        if op == "wgram":
            self.stats.gram_ops += 1
        inputs = self._colocate(inputs, placement)
        salt, _build = self._route(op, inputs)
        key = structural_key(salt, op, meta, self._signature(inputs))
        return self._call(key, inputs, placement, op,
                          lambda: self._lowering(op, meta, inputs))

    def run_program(self, program: Program, inputs: Sequence[Any],
                    placement: Tuple[int, int]) -> Tuple[Any, ...]:
        """Run a segment of block ops as one compiled call.

        The program traces each op with the lowering ``execute`` gives it,
        and passes each op's result through ``lax.optimization_barrier``,
        so XLA neither fuses nor reassociates across block ops: the device
        does the per-op path's work op for op.  Each input is moved to the
        placement's device once, however many ops read it."""
        self.stats.dispatches += 1
        self.stats.programs += 1
        self.stats.lowered_ops += len(program.ops)
        self.stats.gram_ops += program.gram_ops
        inputs = self._colocate(inputs, placement)
        key = (self.name, program.key, self._signature(inputs))
        return self._call(key, inputs, placement, "program",
                          lambda: self._program_fn(program))

    def _call(self, key: tuple, inputs: Sequence[Any],
              placement: Tuple[int, int], label: str,
              make: Callable[[], Callable]):
        """The one compile-cached call protocol: call the executable cached
        under ``key``, or jit ``make()`` and compile it on a miss."""
        fn = self._cache.get(key)
        tr = self.tracer
        if fn is not None:
            self.stats.jit_calls += 1
            if tr is not None:
                tr.record("compile_hit", label, placement[0], placement[1])
            with Span(SPAN_DISPATCH, self.stats, "dispatch_s"):
                return fn(*inputs)
        jitted = self._jax.jit(make())
        self.stats.jit_calls += 1
        with Span(SPAN_COMPILE) as span:
            out = jitted(*inputs)
            self._jax.block_until_ready(out)  # compile_s is compile + first run
        self._cache.put(key, jitted, compile_seconds=span.elapsed)
        if tr is not None:
            tr.record("compile_miss", label, placement[0], placement[1],
                      args={"compile_s": span.elapsed})
        return out

    def _route(self, op: str, inputs: Sequence[Any]
               ) -> Tuple[str, Callable[[str, Dict[str, Any]], Optional[Callable]]]:
        """(compile-cache salt, builder) of the lowering ``op`` takes on
        ``inputs`` (arrays or tracers); subclasses route some ops to their
        own kernels under their own salt."""
        return self._salt, self._build

    def _lowering(self, op: str, meta: Dict[str, Any],
                  inputs: Sequence[Any]) -> Callable:
        build = self._route(op, inputs)[1]
        fn = build(op, meta)
        if fn is None:
            # no silent host round-trip: it would hide the device on the
            # chip path behind numpy
            raise NotImplementedError(
                f"{self.name} backend has no lowering for block op {op!r}")
        if build == self._build and any(x.dtype == np.float32 for x in inputs):
            # a float32 block op computes in float32, as numpy does: XLA's
            # default for a float32 contraction on a TPU is one bfloat16
            # pass, so every product in the lowering is traced at HIGHEST
            # (matmul, tensordot, einsum, wgram, syrk_update and the
            # products of the solves); other ops have no product to change
            return self._at_highest(fn)
        return fn

    def _at_highest(self, fn: Callable) -> Callable:
        precision = self._jax.default_matmul_precision

        def highest(*xs):
            with precision("highest"):
                return fn(*xs)

        return highest

    def _program_fn(self, program: Program) -> Callable:
        barrier = self._jax.lax.optimization_barrier
        ops, outputs = program.ops, program.outputs

        def run(*xs):
            vals = []
            for op, meta, args in ops:
                ins = [vals[j] if j >= 0 else xs[~j] for j in args]
                vals.append(barrier(self._lowering(op, meta, ins)(*ins)))
            return tuple(vals[k] for k in outputs)

        return run

    def _signature(self, inputs) -> Tuple[Tuple[Tuple[int, ...], str], ...]:
        return tuple((tuple(x.shape), str(x.dtype)) for x in inputs)

    def _colocate(self, inputs, placement):
        """Move operands onto the placement's device (no-op on one device;
        the scheduler already minimized these moves — they mirror the
        transfers ``ClusterState.transition`` accounted)."""
        if len(self._devices) == 1:
            return list(inputs)
        dev = self.device_of(placement)
        out = []
        for x in inputs:
            if getattr(x, "devices", None) is not None and x.devices() != {dev}:
                with Span(SPAN_MOVE):
                    x = self._jax.device_put(x, dev)
                self.stats.device_moves += 1
                self.stats.device_move_bytes += x.nbytes
            out.append(x)
        return out

    # -- lowering ------------------------------------------------------------
    def _build(self, op: str, meta: Dict[str, Any]) -> Optional[Callable]:
        """Return a pure jax-traceable callable implementing one block op
        (metadata baked in; shapes/dtypes fixed by the cache key)."""
        jnp = self._jnp
        if op in self._unary:
            return self._unary[op]
        if op in self._binary:
            fn = self._binary[op]
            ea, eb = bool(meta.get("expand_a")), bool(meta.get("expand_b"))

            def binary(a, b, fn=fn, ea=ea, eb=eb):
                if ea:
                    a = a[..., None]
                if eb:
                    b = b[..., None]
                return fn(a, b)

            return binary
        if op == "scalar":
            fn = self._binary[meta["op"]]
            s = meta["scalar"]
            if meta.get("reverse"):
                return lambda x: fn(s, x)
            return lambda x: fn(x, s)
        if op == "matmul":
            ta, tb = bool(meta.get("ta")), bool(meta.get("tb"))

            def matmul(a, b):
                if ta:
                    a = jnp.swapaxes(a, -1, -2)
                if tb:
                    b = jnp.swapaxes(b, -1, -2)
                return a @ b

            return matmul
        if op == "reduce_axis":
            axis = meta["axis"]
            red = {"add": jnp.sum, "maximum": jnp.max, "minimum": jnp.min}[
                meta.get("op", "add")]
            return lambda x: red(x, axis=axis)
        if op == "transpose":
            perm = meta.get("perm")
            return lambda x: jnp.transpose(x, perm)
        if op == "tensordot":
            axes = meta["axes"]
            return lambda a, b: jnp.tensordot(a, b, axes=axes)
        if op == "einsum":
            spec = meta["spec"]
            return lambda *xs: jnp.einsum(spec, *xs)
        if op == "fused":
            chain = meta["chain"]
            return lambda x: apply_chain(x, chain, self._unary, self._binary)
        if op == "qr_r":
            return lambda x: jnp.linalg.qr(x, mode="r")
        if op == "qr_q":
            return lambda x: jnp.linalg.qr(x)[0]
        if op == "qr_stackr":
            return lambda *xs: jnp.linalg.qr(
                jnp.concatenate(xs, axis=0), mode="r")
        if op == "stack":
            return lambda *xs: jnp.concatenate(xs, axis=0)
        if op == "slice_rows":
            start, stop = meta["start"], meta["stop"]
            return lambda x: x[start:stop]
        if op == "slice":
            idx = tuple(slice(int(a), int(b))
                        for a, b in zip(meta["starts"], meta["stops"]))
            return lambda x: x[idx]
        if op == "concat_blocks":
            shape = tuple(int(s) for s in meta["shape"])
            offsets = [tuple(int(o) for o in off) for off in meta["offsets"]]

            def concat_blocks(*pieces):
                out = jnp.zeros(shape, dtype=pieces[0].dtype)
                for off, piece in zip(offsets, pieces):
                    out = out.at[tuple(
                        slice(o, o + s) for o, s in zip(off, piece.shape)
                    )].set(piece)
                return out

            return concat_blocks
        if op == "matricize":
            mode = meta["mode"]
            return lambda x: jnp.moveaxis(x, mode, 0).reshape(
                x.shape[mode], -1)
        if op == "khatri_rao":
            # a broadcast product, one rounding per element as in numpy
            # (``einsum`` would lower it to an outer-product contraction)
            return lambda a, b: (a[:, None, :] * b[None, :, :]).reshape(
                a.shape[0] * b.shape[0], a.shape[1])
        if op == "solve":
            return lambda h, g: jnp.linalg.solve(h, g)
        if op == "rsolve":
            return lambda x, r: jnp.linalg.solve(r.T, x.T).T
        if op == "tsolve":
            return lambda a, b: jnp.linalg.solve(a.T, b)
        if op == "potrf":
            return lambda x: jnp.linalg.cholesky(x)
        if op == "trsm":
            return lambda a, l: jnp.linalg.solve(l, a.T).T
        if op == "syrk_update":
            return lambda c, a, b: c - a @ b.T
        if op == "wgram":
            # the weighting multiply fuses into the contraction's operand:
            # no (m, d) product of x and w is written out
            dims = (((0,), (0,)), ((), ()))
            return lambda x, w: self._jax.lax.dot_general(x * w, x, dims)
        if op == "svd_u":
            return lambda x: jnp.linalg.svd(x, full_matrices=False)[0]
        if op == "svd_s":
            return lambda x: jnp.linalg.svd(x, full_matrices=False)[1]
        if op == "svd_vt":
            return lambda x: jnp.linalg.svd(x, full_matrices=False)[2]
        return None

    @property
    def compile_cache(self) -> Optional[CompileCache]:
        return self._cache
