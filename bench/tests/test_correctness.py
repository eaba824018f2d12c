"""The comparison that decides ``correct``: sound runs pass it; the control
and every fault a cell can have fail it.

Each fault is planted in the library under the timed path, and the run goes
through the harness as on the chip, below its look for a chip.
"""
import json

import numpy as np
import pytest

from bench import harness



@pytest.mark.parametrize("cell", ["logreg_higgs.newton", "dgemm_16k.pallas",
                                  "logreg_higgs_x4.newton"])
def test_sound_run_is_correct(run_tiny, cell):
    result = run_tiny(cell)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    for check in result["checks"].values():
        assert check["value"] <= check["limit"]


@pytest.mark.parametrize("cell", ["logreg_higgs.newton", "dgemm_16k.pallas"])
def test_control_fails_the_limit(tiny_root, cell):
    """The reference in the precision below the configuration's, put in the
    program's place, goes through the cell's own comparison and verdict and
    comes out not correct, on three seeds."""
    c = harness.Cell.load(tiny_root, f"tiny_{cell}")
    for seed in (1, 2, 3):
        job = c.kind.setup(c.config, c.workload["traffic"], seed)
        checks, failed = job.check(job.control())
        assert failed == 1, checks
        assert not harness.is_correct(checks, failed), checks
        assert any(not check.ok for check in checks)


def _patch_build(monkeypatch, cls, method, wrap):
    """Wrap the lowering ``cls.method`` returns: ``wrap(op, meta, fn)``."""
    orig = getattr(cls, method)

    def build(self, op, meta):
        fn = orig(self, op, meta)
        return fn if fn is None else wrap(op, meta, fn)

    monkeypatch.setattr(cls, method, build)


def _jax_backend():
    from repro.backend.jax_backend import JaxBackend

    return JaxBackend


def _pallas_backend():
    from repro.backend.pallas_backend import PallasBackend

    return PallasBackend


def state_unchanged(monkeypatch):
    """Every Newton step is zero: beta stays where it started."""
    _patch_build(monkeypatch, _jax_backend(), "_build",
                 lambda op, meta, fn: (lambda h, g: 0.0 * g) if op == "solve" else fn)


def half_batch(monkeypatch):
    """Block products over rows use the first half of the rows, doubled:
    half of the batch left out, the mean taken over the rest."""
    def wrap(op, meta, fn):
        if op != "matmul":
            return fn

        def halved(a, b):
            if meta.get("ta"):  # X^T v: the rows are the contraction
                k = a.shape[0] // 2
                return 2.0 * fn(a[:k], b[:k])
            k = a.shape[1] // 2
            return 2.0 * fn(a[:, :k], b[:k])
        return halved

    _patch_build(monkeypatch, _jax_backend(), "_build", wrap)
    _patch_build(monkeypatch, _pallas_backend(), "_build_pallas_matmul", wrap)


def exchange_left_out(monkeypatch):
    """Operands on another chip are used where they lie, never moved."""
    monkeypatch.setattr(_jax_backend(), "_colocate",
                        lambda self, inputs, placement: list(inputs))


def answer_altered(monkeypatch):
    """The coefficients are nudged where each Newton update makes them, and
    every block product is scaled by 1 + 1e-4 where the kernel makes it."""
    def newton(op, meta, fn):
        if op != "sub":
            return fn

        def nudged(a, b):
            out = fn(a, b)
            return out.at[0, 0].add(1e-3) if out.shape == (28, 1) else out
        return nudged

    _patch_build(monkeypatch, _jax_backend(), "_build", newton)
    _patch_build(monkeypatch, _pallas_backend(), "_build_pallas_matmul",
                 lambda op, meta, fn: lambda a, b: fn(a, b) * (1.0 + 1e-4))


FAULTS = {
    "logreg_higgs.newton": [state_unchanged, half_batch, answer_altered],
    "dgemm_16k.pallas": [half_batch, answer_altered],
    "logreg_higgs_x4.newton": [state_unchanged, half_batch, exchange_left_out,
                               answer_altered],
}


@pytest.mark.parametrize("cell,fault", [(c, f) for c, fs in FAULTS.items()
                                        for f in fs],
                         ids=lambda x: getattr(x, "__name__", x))
def test_fault_is_not_correct(run_tiny, monkeypatch, cell, fault):
    fault(monkeypatch)
    result = run_tiny(cell)
    assert result["correct"] is False, result
    assert result["failed"] >= 1


def test_checks_are_printed_last(capsys):
    checks = [harness.Check("beta_rel_err", 3e-8, 1e-6),
              harness.Check("grad_norm", float("nan"), 1.0)]
    harness.report({"correct": False, "checks": {}}, checks)
    out, err = capsys.readouterr()
    assert err.splitlines()[-2:] == [
        "check beta_rel_err: 3e-08 limit 1e-06 ok",
        "check grad_norm: nan limit 1.0 FAILED"]
    assert json.loads(out.splitlines()[-1]) == {"correct": False, "checks": {}}
    assert not checks[1].ok and np.isnan(checks[1].value)
