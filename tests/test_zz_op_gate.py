"""Op-validation ledger GATE (ref: org.nd4j.autodiff.validation.OpValidation
— "fails CI if an op has no test", SURVEY §4.1).

The filename sorts last so this runs after every validation tier
(test_op_coverage, test_ops, test_op_validation_r3, test_wide_ops,
test_graph_op_sweep) has marked its ops in the in-process ledger. A full-suite
run must leave ZERO unvalidated ops; any op added to the registry without a
validating test fails here.

Exemptions must be listed in EXEMPT with an inline justification — none are
currently needed.
"""
import os
import sys

import pytest

from deeplearning4j_tpu.ops import coverage_report
from deeplearning4j_tpu.ops.registry import REGISTRY

# The validation tiers whose in-process run closes the ledger. Enforcement
# requires ALL of them to have been collected in this pytest process —
# a partial run (e.g. `pytest tests/test_ndarray.py tests/test_zz_op_gate.py`)
# skips instead of failing with hundreds of false "unvalidated op" entries
# (round-4 advisor finding). The registry-size pin below still runs on every
# invocation as the tamper check.
TIER_MODULES = ("test_op_coverage", "test_ops", "test_op_validation_r3",
                "test_wide_ops", "test_graph_op_sweep")

# op-key -> justification. Keep empty unless an op genuinely cannot be
# validated in CI (document why inline).
EXEMPT: dict = {}

# Registry-size pin: adding an op REQUIRES updating this number in the same
# change — which forces this gate into the diff, and the gate then demands a
# validating test for the new op. (Round-3 verdict: the old `len(done) < 400`
# soft floor let 50 ops lose their tests before the gate noticed, and a
# partial-suite run silently skipped enforcement.)
# 450 = the reference's declarable-op count (parity, rounds 1-4);
# +1 round-5 beyond-parity op: scaledDotProductAttentionFused, the target
# of the SameDiff attention-fusion rewrite (autodiff/rewrites.py)
EXPECTED_OPS = 451


def test_registry_size_pinned():
    assert len(REGISTRY) == EXPECTED_OPS, (
        f"op registry has {len(REGISTRY)} ops, gate expects {EXPECTED_OPS}. "
        "If you added ops: add validating tests (oracle + gradient + graph "
        "parity) that mark_validated() each one, then bump EXPECTED_OPS "
        "here in the same change.")


def test_ledger_is_closed():
    done, todo = coverage_report()
    assert len(done) + len(todo) == len(REGISTRY)
    if os.environ.get("PYTEST_XDIST_WORKER"):
        # the ledger is per process: under pytest-xdist each worker has run
        # some of the tier files and this one holds a part of the marks, so
        # which ops read "unvalidated" depends on how the files were dealt
        pytest.skip("the ledger is per process and this is one xdist worker "
                    "of several — run the suite in one process for "
                    "ledger enforcement")
    missing_tiers = [m for m in TIER_MODULES if m not in sys.modules]
    if missing_tiers:
        pytest.skip(f"validation tiers not in this run: {missing_tiers} — "
                    "run the full suite for ledger enforcement")
    if not done:
        # tier modules were COLLECTED (imported) but their bodies were
        # deselected (-k/-m/--deselect): nothing marked, nothing to enforce
        pytest.skip("validation tiers collected but deselected — "
                    "run the full suite for ledger enforcement")
    open_items = [k for k in todo if k not in EXEMPT]
    assert not open_items, (
        f"{len(open_items)} registry ops have no validating test: "
        f"{open_items}\nEither add a test that mark_validated()s each op "
        f"(oracle + gradient + graph parity, see test_op_validation_r3.py) "
        f"or add an EXEMPT entry with a justification.")
    stale = [k for k in EXEMPT if k not in todo]
    assert not stale, f"EXEMPT entries now validated — remove: {stale}"
