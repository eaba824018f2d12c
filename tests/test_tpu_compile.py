"""Compiles of the block runtime's main path for a described TPU v5e chip.

Nothing here runs on a chip: each test compiles one kernel or block op for
the first chip of a described ``v5e:2x2`` topology, at the shapes the
benchmark cells under ``bench/`` run, and fails where the chip's compiler
would refuse it.  The topology is described inside a fixture, never at
import, so every test worker collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# one of the eight row blocks of an 11 000 000 x 28 HIGGS-shaped design matrix
HIGGS_BLOCK = (1_375_000, 28)
HIGGS_COLUMN = (HIGGS_BLOCK[0], 1)  # a row block of y, mu, w or mu - y
# the CP-ALS cell: a 768^3 float32 tensor in eight mode-0 slabs, rank 64
CPALS_N, CPALS_SLABS, CPALS_RANK = 768, 8, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler can be loaded here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without the chip: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def jax_backend():
    from repro.backend.jax_backend import JaxBackend

    return JaxBackend("float32")


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_pallas_matmul_compiles_to_mosaic(one_chip):
    from repro.kernels.ops import matmul

    compiled = _compile(lambda a, b: matmul(a, b, interpret=False), one_chip,
                        (4096, 4096), (4096, 4096))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op, meta, shapes", [
    ("wgram", {}, [HIGGS_BLOCK, HIGGS_COLUMN]),             # X^T diag(w) X
    ("matmul", {"ta": True}, [HIGGS_BLOCK, HIGGS_COLUMN]),  # X^T (mu - y)
    ("matmul", {}, [HIGGS_BLOCK, (28, 1)]),                 # X @ beta
    ("sigmoid", {}, [HIGGS_COLUMN]),                        # mu
    ("solve", {}, [(28, 28), (28, 1)]),                     # Newton step
], ids=["wgram", "xt_r", "x_beta", "sigmoid", "solve"])
def test_newton_block_op_compiles(one_chip, jax_backend, op, meta, shapes):
    compiled = _compile(jax_backend._build(op, meta), one_chip, *shapes)
    mem = compiled.memory_analysis()
    # every operand and result of a block op fits one chip's 16 GB of HBM
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9


def test_weighted_gram_writes_no_n_by_d_array(one_chip, jax_backend):
    """The Hessian's block op at HIGHEST, as it runs: the weighting multiply
    fuses into the contraction's operand, so the chip neither writes nor
    reads back an n x d product of w and X."""
    import re

    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in (HIGGS_BLOCK, HIGGS_COLUMN)]
    compiled = jax.jit(jax_backend._lowering("wgram", {}, args)).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    rows, cols = HIGGS_BLOCK
    assert not re.search(rf"= f32\[{rows},{cols}\]\S* fusion\(", entry), entry
    assert "operand_precision={highest,highest}" in compiled.as_text()


def test_lowered_newton_programs_compile(one_chip, jax_backend):
    """Every segment program of a Newton fit on eight row blocks (the plans
    the one-chip HIGGS cell replays) compiles at HIGGS block shapes, and the
    largest fits one chip beside nothing else."""
    import numpy as np

    from repro.core import ArrayContext, ClusterSpec
    from repro.glm import GLM

    rows = 1_000  # rows of a block in the fit that yields the programs
    ctx = ArrayContext(cluster=ClusterSpec(1, 8), node_grid=(1, 1),
                       backend="jax", dtype="float32", pipeline=True,
                       plan_cache=True, gc=True)
    be = ctx.executor.backend
    seen = {}
    run_program = be.run_program

    def spy(program, inputs, placement):
        seen.setdefault(program.key, (program, [x.shape for x in inputs]))
        return run_program(program, inputs, placement)

    be.run_program = spy
    rng = np.random.default_rng(0)
    y = (rng.random((8 * rows, 1)) < 0.5).astype(np.float64)
    X = rng.standard_normal((8 * rows, HIGGS_BLOCK[1])) + 0.25 * (y - 0.5)
    GLM(ctx, max_iter=10, tol=1.0, reg=1e-6).fit(
        ctx.from_numpy(X, grid=(8, 1)), ctx.from_numpy(y, grid=(8, 1)))
    assert len(seen) >= 8
    largest = 0
    for program, shapes in seen.values():
        shapes = [tuple(HIGGS_BLOCK[0] if d == rows else d for d in s)
                  for s in shapes]
        mem = _compile(jax_backend._program_fn(program), one_chip,
                       *shapes).memory_analysis()
        largest = max(largest, mem.argument_size_in_bytes
                      + mem.output_size_in_bytes + mem.temp_size_in_bytes)
    # the largest reads X and one column (the weighted Gram: X and w, or
    # X^T (mu - y)), 1.45 GB at the chip's padded layout; an n x d
    # temporary beside X, such as w * X, would take it past 2.8 GB
    assert 1.3e9 < largest < 2e9


def test_lowered_cpals_programs_compile(one_chip, jax_backend):
    """Every segment program of a CP-ALS fit (the plans the one-chip CP-ALS
    cell replays) compiles at the cell's slab shapes, each product in it at
    HIGHEST, and the largest fits one chip.

    The programs come from a fit of a 16^3 tensor in the cell's eight slabs
    at rank 3: each coordinate in a slice or concatenation is scaled by 48
    to 768^3, and each operand dimension taken to its size in the cell.
    The largest, a layout change of the tensor, reads it (1.81 GB), writes
    it (1.81 GB) and holds its slices (1.39-1.85 GB of temporaries)."""
    import re

    import numpy as np

    from repro.backend.base import Program
    from repro.core import ArrayContext, ClusterSpec
    from repro.factor import cp_als

    n, rank = 16, 3
    scale = CPALS_N // n
    size = {n // CPALS_SLABS: CPALS_N // CPALS_SLABS, n: CPALS_N,
            n * n: CPALS_N ** 2, rank: CPALS_RANK}
    ctx = ArrayContext(cluster=ClusterSpec(1, CPALS_SLABS), node_grid=(1, 1, 1),
                       backend="jax", dtype="float32", pipeline=True,
                       plan_cache=True, gc=True)
    be = ctx.executor.backend
    seen = {}
    run_program = be.run_program

    def spy(program, inputs, placement):
        seen.setdefault(program.key, (program, [x.shape for x in inputs]))
        return run_program(program, inputs, placement)

    be.run_program = spy
    X = ctx.from_numpy(np.random.default_rng(0).standard_normal((n, n, n)),
                       grid=(CPALS_SLABS, 1, 1))
    res = cp_als(X, rank=rank, iters=2, seed=1, track_fit=False)
    [f.to_numpy() for f in res.factors]

    def scaled(values):
        return tuple(int(v) * scale for v in values)

    def at_cell_size(program):
        ops = []
        for op, meta, args in program.ops:
            if op == "slice":
                meta = dict(meta, starts=scaled(meta["starts"]),
                            stops=scaled(meta["stops"]))
            elif op == "concat_blocks":
                meta = dict(meta, shape=scaled(meta["shape"]),
                            offsets=tuple(scaled(o) for o in meta["offsets"]))
            ops.append((op, meta, args))
        return Program(tuple(ops), program.outputs)

    assert len(seen) >= 8
    largest = 0
    products = 0
    for program, shapes in seen.values():
        shapes = [tuple(size[d] for d in s) for s in shapes]
        compiled = _compile(jax_backend._program_fn(at_cell_size(program)),
                            one_chip, *shapes)
        mem = compiled.memory_analysis()
        largest = max(largest, mem.argument_size_in_bytes
                      + mem.output_size_in_bytes + mem.temp_size_in_bytes)
        for line in compiled.as_text().splitlines():
            if re.search(r"= \S+ (convolution|dot)\(", line):
                products += 1
                assert "operand_precision={highest,highest}" in line, line
    assert products >= 3  # the Grams, the MTTKRP, the solve
    assert 5e9 < largest < 16e9
