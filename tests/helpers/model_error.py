"""Shared predicted-vs-measured assertion — ONE tolerance source.

Every place that checks a §5 prediction against a measurement (unit tests,
subprocess helpers, the benchmark matrix's regression gate) must price
drift the same way, or the test suite and the CI gate diverge silently.
This helper is that single seam: the *metric* and the *budgets* both live
in ``repro.comm.perfmodel`` (``model_error`` / ``error_budget``), and this
module only adds the assertion ergonomics tests want.

Import patterns served:
* pytest files: ``from helpers.model_error import assert_model_error``
  (``tests/`` is on the configured pythonpath);
* subprocess helper scripts run from ``tests/helpers``:
  ``import model_error``.
"""
from __future__ import annotations

from repro.comm.perfmodel import error_budget, model_error

__all__ = ["model_error", "error_budget", "assert_model_error"]


def assert_model_error(measured: float, predicted: float, *,
                       budget: float | None = None, cell: dict | None = None,
                       label: str = "") -> float:
    """Assert ``model_error(measured, predicted) <= budget`` and return the
    error.

    ``budget`` may be given explicitly (exact-identity checks pass ~1e-9;
    the paper-table reproductions pass their published rtol) or derived
    from a matrix ``cell`` mapping via ``perfmodel.error_budget`` — the
    same call the benchmark gate makes, so a budget loosened for the bench
    is automatically loosened for the tests and vice versa.
    """
    if budget is None:
        budget = error_budget(cell or {})
    err = model_error(measured, predicted)
    assert err <= budget, (
        f"model error {err:.4g} exceeds budget {budget:.4g}"
        f"{' [' + label + ']' if label else ''}: "
        f"measured={measured:.6g} predicted={predicted:.6g}")
    return err
