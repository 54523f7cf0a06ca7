"""The kernel/oracle parity registry: every jitted kernel in ops/ and
the NumPy twin that referees it.

The paper's whole bet is that scheduling decisions can move onto the
accelerator WITHOUT changing them — so a jitted kernel without a host
oracle is an unreviewable kernel. This module is the machine-checkable
ledger of that contract. ktlint's KT006 pass (tools/ktlint/
rules_parity.py) statically cross-checks it against the tree:

- every ``jax.jit``-decorated function under ``kubernetes_tpu/ops/``
  must appear as a key here;
- every entry's ``oracle`` must resolve to a real function (dotted
  path relative to ``kubernetes_tpu/``, or ``tests.`` for test-local
  helpers);
- every entry's ``suite`` file must exist and actually mention the
  kernel (or its ``exercised_as`` public wrapper, or the oracle) — a
  registered-but-never-run twin is as useless as no twin.

``tests/test_ktsan.py`` additionally imports this registry at runtime
and asserts every reference resolves via getattr, so a rename cannot
rot the ledger between static sweeps.

Keys are ``<ops module>.<dotted def path>`` (nested jits include their
enclosing function: ``preemption._victim_prefix_kernel.kernel``).

KT006 intentionally has no baseline: a new kernel lands WITH its twin
or it does not land. Use ``exercised_as`` when the suite drives the
kernel through a public wrapper rather than by its private name.

Every key here ALSO needs a shape/dtype/sharding contract in
``kubernetes_tpu/ops/contracts.py`` (CONTRACTS) — the ktshape checker
(``python -m tools.ktlint --kernel-contracts``) enforces completeness
in both directions, so this registry and the contract registry are one
kernel inventory with two faces: the twin referees the DECISIONS, the
contract pins the INTERFACE (bucket lattices, oracle dtypes, pod-axis
coupling class).
"""

from __future__ import annotations

# NOTE: must stay a literal dict — KT006 reads it by AST, without
# importing jax.
ORACLE_TWINS = {
    "capacity.capacity_report": {
        # Bit-exact twin (int32-quantized reductions): the parity suite
        # asserts array_equal on every leaf, no tolerance.
        "oracle": "ops.oracle.capacity_report_numpy",
        "suite": "tests/test_solver_parity.py",
    },
    "incremental._scatter_rows": {
        "oracle": "ops.oracle.scatter_rows_numpy",
        "suite": "tests/test_ktsan.py",
    },
    "matrices.gang_member_counts": {
        "oracle": "scheduler.gang.member_counts_host",
        "suite": "tests/test_gang.py",
    },
    "pallas_scan._solve_packed": {
        # Parity chain: pallas == XLA scan (bit-exact, its suite) and
        # XLA scan == sequential NumPy oracle (test_solver_parity.py).
        "oracle": "ops.oracle.solve_sequential_numpy",
        "suite": "tests/test_pallas_scan.py",
        "exercised_as": "solve_with_state_pallas",
    },
    "pallas_scan.scatter_rows_banded": {
        "oracle": "ops.oracle.scatter_rows_banded_numpy",
        "suite": "tests/test_ktsan.py",
    },
    "preemption._victim_prefix_kernel.kernel": {
        "oracle": "scheduler.batch.preempt_backlog_scalar",
        "suite": "tests/test_solver_parity.py",
        "exercised_as": "preempt_backlog_scalar",
    },
    "rebalance.plan_moves": {
        # Bit-exact twin (the capacity plane's int32-quantized fit
        # math + a Python rewrite of the lax.scan): array_equal on
        # every leaf, no tolerance.
        "oracle": "ops.oracle.plan_moves_numpy",
        "suite": "tests/test_solver_parity.py",
    },
    "sinkhorn.solve_sinkhorn_stats": {
        "oracle": "ops.oracle.validate_assignment_numpy",
        "suite": "tests/test_sinkhorn.py",
        "exercised_as": "solve_sinkhorn",
    },
    "sinkhorn.solve_sinkhorn_with_state": {
        "oracle": "ops.oracle.validate_assignment_numpy",
        "suite": "tests/test_sinkhorn.py",
        "exercised_as": "sinkhorn_assignments",
    },
    "solver._solve_xla": {
        "oracle": "ops.oracle.solve_sequential_numpy",
        "suite": "tests/test_solver_parity.py",
    },
    "solver._solve_with_state_xla": {
        "oracle": "ops.oracle.solve_sequential_numpy",
        "suite": "tests/test_solver_parity.py",
    },
    "solver.explain_rows": {
        "oracle": "ops.oracle.explain_bits_numpy",
        "suite": "tests/test_solver_parity.py",
    },
    "wave.solve_waves": {
        "oracle": "ops.oracle.validate_assignment_numpy",
        "suite": "tests/test_wave.py",
    },
    "wave.solve_waves_with_state": {
        "oracle": "ops.oracle.validate_assignment_numpy",
        "suite": "tests/test_wave.py",
        "exercised_as": "solve_waves_with_state",
    },
}
