"""Readings that set the limits of ``correct`` (kbench/reference.py):
the program's own runs, the control, and the planted faults, many
seeds in one process so that set-up is paid once per kind.

    python -m kbench.control --workload <name> --seeds 1,2,3 --seconds 10 \\
        --kind program|control|unchanged_state|half_batch|altered_answer \\
        [--rate <pods/s>]

- ``program``: the cell as it runs.
- ``control``: the program with one guarantee of the configuration
  broken, the step a later change would be tempted by: the session
  drops one default priority, the one that decides this cell's
  placements (the traffic's ``control.weights``, in the order
  LeastRequested, BalancedResourceAllocation, ServiceSpreading; the
  program has no such switch).
- ``--rate`` overrides an open-loop mix's rate: the sweep that finds
  the knee.
- the faults, planted in the session under the timed path:
  ``unchanged_state`` (each solve hands back the node state it was
  given), ``half_batch`` (half of each tick's pods are dropped),
  ``altered_answer`` (each decision replaced by the first node: with
  identical nodes a move to a neighbour is most often a tie, and so no
  fault by the reference's semantics).

The benchmark's own runs never run this. Prints one JSON line per run:
the seed, ``correct`` and the checks.
"""

from __future__ import annotations

import argparse
import json
import sys

from kbench import run


def plant(kind: str, traffic: dict) -> None:
    """Break the session under the timed path (kubernetes_tpu.ops.
    incremental.SolverSession) for the rest of the process."""
    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.ops import incremental

    S = incremental.SolverSession
    if kind == "control":
        init = S.__init__

        def __init__(self, *a, **kw):
            init(self, *a, **kw)
            self.weights = tuple(traffic["control"]["weights"])

        S.__init__ = __init__
    elif kind == "unchanged_state":
        dispatch = S._dispatch

        def _dispatch(self, pods, carry):
            kept = jax.tree_util.tree_map(jnp.copy, carry)
            assignment, _, tele = dispatch(self, pods, carry)
            return assignment, kept, tele

        S._dispatch = _dispatch
    elif kind == "altered_answer":
        dispatch = S._dispatch

        def _dispatch(self, pods, carry):
            assignment, carry, tele = dispatch(self, pods, carry)
            return jnp.where(assignment >= 0, 0, assignment), carry, tele

        S._dispatch = _dispatch
    elif kind == "half_batch":
        solve_async = S.solve_async

        def _solve_async(self):
            self._pending = self._pending[: (len(self._pending) + 1) // 2]
            return solve_async(self)

        S.solve_async = _solve_async
    else:
        raise ValueError(f"unknown fault {kind!r}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--kind", default="program")
    p.add_argument("--rate", type=float)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    spec = run.load_cell(args.workload, args.rehearse)
    if args.rate:
        spec["traffic"]["rate_per_s"] = args.rate
    device, count = run.prepare(spec["cell"]["chips"], args.rehearse)
    if args.kind != "program":
        plant(args.kind, spec["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(spec, seed, args.seconds, False, device, count)
        print(json.dumps({
            "kind": args.kind, "seed": seed, "rate": spec["traffic"].get("rate_per_s"),
            "correct": res["correct"],
            "checks": res["checks"],
            "readings": res["readings"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
