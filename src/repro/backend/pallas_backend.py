"""The pallas backend: blocked-MXU matmul kernels under the jax backend.

Routes 2-D ``matmul`` block ops through the Pallas kernel
(``repro.kernels.ops.matmul`` -> ``kernels.matmul.matmul_pallas``): explicit
VMEM tiling and an MXU-aligned grid on TPU, ``interpret=True`` everywhere
else so the same kernel body runs (and is tested) on CPU.  Every other op —
and the 1-D matmul/dot forms the block graphs emit for vectors — falls back
to the parent jax backend's XLA lowering, so a mixed graph transparently
splits between hand-written kernels and XLA.

Kernel compilations share the same structural compile cache as the jax
backend under a distinct flavor salt (``"pallas"``), so a pallas matmul and
an XLA matmul of identical structure cache separately while all non-matmul
ops share the jax backend's entries.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

from .jax_backend import JaxBackend


class PallasBackend(JaxBackend):
    name = "pallas"

    def _route(self, op: str, inputs: Sequence[Any]):
        if op == "matmul" and all(x.ndim == 2 for x in inputs):
            return "pallas", self._build_pallas_matmul
        return super()._route(op, inputs)

    def _build_pallas_matmul(self, op: str, meta: Dict[str, Any]):
        jnp = self._jnp
        ta, tb = bool(meta.get("ta")), bool(meta.get("tb"))

        def pallas_matmul(a, b):
            from repro.kernels.ops import matmul as kernel_matmul

            if ta:
                a = jnp.swapaxes(a, -1, -2)
            if tb:
                b = jnp.swapaxes(b, -1, -2)
            return kernel_matmul(a, b)

        return pallas_matmul
