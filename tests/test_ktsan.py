"""ktsan: the lock-order/deadlock sanitizer, both halves.

Runtime (utils/sanitizer.py): every detector is proven to FIRE on a
deliberate violation — a lock-order inversion, a blocking call under a
lock, an Event.wait without timeout, a jit-dispatch hook under a lock,
a lock held by a dead thread — and to stay quiet on the sanctioned
shapes (io_gate locks, allow_blocking grants, RLock re-entry).

Static (tools/ktlint/lockgraph.py + KT006): fixture trees prove the
interprocedural detectors fire (inversion cycle, ``*_locked`` caller
without the lock, ``*_locked`` re-acquire, unregistered jitted
kernel), pragmas suppress with a reason, runtime/static graphs merge
on node names — and the LIVE tree is gated clean (the acceptance
criterion: zero cycles, zero contract violations).

Plus the satellites that ride the same machinery: the kernel/oracle
registry resolves at runtime, _scatter_rows has NumPy parity with its
registered twin, and the recompilation sentinel pins the pow2
bucketing contract by counting actual XLA compiles.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from kubernetes_tpu.utils import sanitizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.ktlint import lockgraph  # noqa: E402
from tools.ktlint.rules_parity import (  # noqa: E402
    OracleTwinRule,
    jitted_kernels,
    resolve_oracle,
)
import ast  # noqa: E402
import pathlib  # noqa: E402

from tools.ktlint.framework import FileContext  # noqa: E402


# -- runtime: lock-order graph -----------------------------------------


class TestRuntimeLockOrder:
    def test_inversion_is_a_finding(self):
        a = sanitizer.lock("fxrt.a")
        b = sanitizer.lock("fxrt.b")
        with a:
            with b:
                pass

        def reversed_order():
            with b:
                with a:
                    pass

        t = threading.Thread(target=reversed_order)
        t.start()
        t.join()
        kinds = [f["kind"] for f in sanitizer.findings()]
        assert "lock-order-cycle" in kinds, sanitizer.findings()
        cyc = next(
            f for f in sanitizer.findings()
            if f["kind"] == "lock-order-cycle"
        )
        assert set(cyc["cycle"]) >= {"fxrt.a", "fxrt.b"}
        sanitizer.reset()

    def test_consistent_order_is_clean(self):
        a = sanitizer.lock("fxrt.c1")
        b = sanitizer.lock("fxrt.c2")
        for _ in range(3):
            with a:
                with b:
                    pass
        assert sanitizer.findings() == []

    def test_sibling_instances_same_name_not_an_edge(self):
        # Two stores' kvstore.lock taken nested must not self-cycle.
        s1 = sanitizer.lock("fxrt.sib")
        s2 = sanitizer.lock("fxrt.sib")
        with s1:
            with s2:
                pass
        assert sanitizer.findings() == []
        assert not any(
            e["from"] == e["to"] == "fxrt.sib" for e in sanitizer.edges()
        )

    def test_rlock_reentry_is_not_an_edge(self):
        r = sanitizer.rlock("fxrt.re")
        with r:
            with r:
                assert r._is_owned()
        assert sanitizer.findings() == []


# -- runtime: blocking under a lock ------------------------------------


class TestRuntimeBlocking:
    def test_fsync_under_lock_fires(self, tmp_path):
        lk = sanitizer.lock("fxrt.fs")
        f = open(tmp_path / "x", "w")
        f.write("x")
        f.flush()
        with lk:
            os.fsync(f.fileno())
        f.close()
        found = [
            f for f in sanitizer.findings()
            if f["kind"] == "blocking-under-lock" and f["op"] == "fsync"
        ]
        assert found and "fxrt.fs" in found[0]["locks"]
        sanitizer.reset()

    def test_io_gate_lock_is_exempt(self, tmp_path):
        gate = sanitizer.lock("fxrt.gate", io_gate=True)
        f = open(tmp_path / "x", "w")
        f.write("x")
        f.flush()
        with gate:
            os.fsync(f.fileno())
        f.close()
        assert sanitizer.findings() == []

    def test_allow_blocking_grant(self, tmp_path):
        lk = sanitizer.lock("fxrt.grant")
        f = open(tmp_path / "x", "w")
        f.write("x")
        f.flush()
        with lk:
            with sanitizer.allow_blocking("fixture: documented exception"):
                os.fsync(f.fileno())
        f.close()
        assert sanitizer.findings() == []

    def test_event_wait_no_timeout_under_lock_fires(self):
        lk = sanitizer.lock("fxrt.evw")
        ev = threading.Event()
        ev.set()  # wait() returns immediately; the CALL is the finding
        with lk:
            ev.wait()
        assert any(
            f["op"] == "event-wait-no-timeout" for f in sanitizer.findings()
        ), sanitizer.findings()
        sanitizer.reset()

    def test_event_wait_with_timeout_is_fine(self):
        lk = sanitizer.lock("fxrt.evt")
        ev = threading.Event()
        with lk:
            ev.wait(timeout=0.001)
        assert sanitizer.findings() == []

    def test_jit_dispatch_hook_under_lock_fires(self):
        lk = sanitizer.lock("fxrt.jit")
        sanitizer.check_blocking("jit-dispatch", "free")  # no lock: quiet
        assert sanitizer.findings() == []
        with lk:
            sanitizer.check_blocking("jit-dispatch", "under lock")
        assert any(
            f["op"] == "jit-dispatch" for f in sanitizer.findings()
        )
        sanitizer.reset()

    def test_blocking_only_observes_sanitized_locks(self, tmp_path):
        # A plain threading.Lock is invisible — adoption via the
        # factory is what opts a component in.
        plain = threading.Lock()
        f = open(tmp_path / "x", "w")
        f.write("x")
        f.flush()
        with plain:
            os.fsync(f.fileno())
        f.close()
        assert sanitizer.findings() == []


# -- runtime: leaks -----------------------------------------------------


class TestRuntimeLeaks:
    def test_lock_held_by_dead_thread_is_leaked(self):
        lk = sanitizer.lock("fxrt.leak")

        def die_holding():
            lk.acquire()

        t = threading.Thread(target=die_holding)
        t.start()
        t.join()
        leaks = sanitizer.leaked_locks()
        assert ("fxrt.leak" in [name for _t, name in leaks]), leaks
        # Clean up so the conftest guard doesn't (rightly) fail us.
        sanitizer.purge_dead_threads()
        lk._inner.release() if hasattr(lk, "_inner") else None
        assert sanitizer.leaked_locks() == []

    def test_held_locks_snapshot(self):
        lk = sanitizer.lock("fxrt.held")
        with lk:
            assert ("fxrt.held" in [n for _t, n in sanitizer.held_locks()])
        assert "fxrt.held" not in [n for _t, n in sanitizer.held_locks()]


# -- runtime: factory cost when off ------------------------------------


def test_factory_returns_plain_locks_when_off():
    # The guard fixture enabled the sanitizer for this module; flip it
    # off around the assertion (enable() restores instrumented mode).
    sanitizer.disable()
    try:
        lk = sanitizer.lock("noop")
        rk = sanitizer.rlock("noop")
        assert type(lk) is type(threading.Lock())
        assert isinstance(rk, type(threading.RLock()))
    finally:
        sanitizer.enable()


# -- static: fixtures ---------------------------------------------------


INVERSION_SRC = """
from kubernetes_tpu.utils import sanitizer

class B:
    def __init__(self):
        self._lock = sanitizer.lock("fx.b")

class A:
    def __init__(self):
        self._lock = sanitizer.lock("fx.a")
        self._b = B()

    def ab(self):
        with self._lock:
            with self._b._lock:
                pass

class C:
    def __init__(self):
        self._a = A()
        self._b = B()

    def ba(self):
        with self._b._lock:
            with self._a._lock:
                pass
"""

LOCKED_SRC = """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def _bump_locked(self):
        self._n += 1

    def good(self):
        with self._lock:
            self._bump_locked()

    def bad(self):
        self._bump_locked()
"""

REACQUIRE_SRC = """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def _oops_locked(self):
        with self._lock:
            pass
"""

CLEAN_SRC = """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._aux = threading.Lock()
        self._n = 0

    def _bump_locked(self):
        self._n += 1

    def work(self):
        with self._lock:
            with self._aux:
                self._bump_locked()
"""


def _analyze_src(tmp_path, src, name="fx.py", runtime=None):
    p = tmp_path / name
    p.write_text(src)
    return lockgraph.analyze([p], runtime=runtime)


class TestStaticLockGraph:
    def test_deliberate_inversion_is_a_cycle(self, tmp_path):
        rep = _analyze_src(tmp_path, INVERSION_SRC)
        assert rep.cycles, rep.render()
        assert set(rep.cycles[0]["nodes"]) == {"fx.a", "fx.b"}
        assert rep.exit_code == 1

    def test_locked_caller_without_lock_fires(self, tmp_path):
        rep = _analyze_src(tmp_path, LOCKED_SRC)
        assert [v.rule for v in rep.violations] == ["KTSAN02"]
        assert "bad" not in rep.violations[0].message  # message names callee
        assert "_bump_locked" in rep.violations[0].message

    def test_locked_caller_pragma_suppresses(self, tmp_path):
        src = LOCKED_SRC.replace(
            "        self._bump_locked()\n"
            "\n"
            "    def bad(self):\n"
            "        self._bump_locked()",
            "        self._bump_locked()\n"
            "\n"
            "    def bad(self):\n"
            "        self._bump_locked()  # ktlint: disable=KTSAN02",
        )
        rep = _analyze_src(tmp_path, src)
        assert rep.violations == [] and rep.suppressed == 1

    def test_locked_body_reacquire_fires(self, tmp_path):
        rep = _analyze_src(tmp_path, REACQUIRE_SRC)
        assert [v.rule for v in rep.violations] == ["KTSAN03"]

    def test_clean_nesting_passes_and_extracts_edges(self, tmp_path):
        rep = _analyze_src(tmp_path, CLEAN_SRC)
        assert rep.violations == [] and rep.cycles == []
        pairs = {(e.src, e.dst) for e in rep.edges}
        assert ("fx.S._lock", "fx.S._aux") in pairs

    def test_init_is_exempt(self, tmp_path):
        src = LOCKED_SRC.replace(
            "    def bad(self):\n        self._bump_locked()",
            "",
        ) + (
            "\n"
            "class T(S):\n"
            "    def __init__(self):\n"
            "        super().__init__()\n"
            "        self._bump_locked()\n"
        )
        rep = _analyze_src(tmp_path, src)
        assert rep.violations == []

    def test_runtime_graph_merges_into_cycle(self, tmp_path):
        # Static half of the cycle from the fixture, runtime half from
        # a sanitizer report: only together do they close the loop.
        src = INVERSION_SRC.replace(
            "    def ba(self):\n"
            "        with self._b._lock:\n"
            "            with self._a._lock:\n"
            "                pass\n",
            "    def ba(self):\n"
            "        pass\n",
        )
        rep = _analyze_src(tmp_path, src)
        assert rep.cycles == []
        runtime = {
            "edges": [
                {"from": "fx.b", "to": "fx.a", "count": 3,
                 "site": "observed in test run"}
            ],
            "findings": [],
        }
        rep2 = _analyze_src(tmp_path, src, runtime=runtime)
        assert rep2.cycles and set(rep2.cycles[0]["nodes"]) == {
            "fx.a", "fx.b"
        }

    def test_runtime_findings_fail_the_gate(self, tmp_path):
        rep = _analyze_src(
            tmp_path, CLEAN_SRC,
            runtime={"edges": [], "findings": [
                {"kind": "blocking-under-lock", "op": "fsync",
                 "locks": ["x"]}
            ]},
        )
        assert rep.exit_code == 1


# -- static: KT006 ------------------------------------------------------


def _ops_ctx(src, relpath):
    tree = ast.parse(src)
    return FileContext(
        pathlib.Path("/nonexistent"), relpath, tree, src.splitlines()
    )


class TestKT006:
    def test_unregistered_kernel_fires(self):
        src = (
            "import functools\n"
            "import jax\n"
            "@functools.partial(jax.jit, static_argnames=('n',))\n"
            "def brand_new_kernel(x, n):\n"
            "    return x\n"
        )
        ctx = _ops_ctx(src, "kubernetes_tpu/ops/fake.py")
        findings = OracleTwinRule().check(ctx)
        assert [f.rule for f in findings] == ["KT006"]
        assert "fake.brand_new_kernel" in findings[0].message

    def test_nested_jit_is_found(self):
        src = (
            "import functools\n"
            "import jax\n"
            "def factory():\n"
            "    @jax.jit\n"
            "    def kernel(x):\n"
            "        return x\n"
            "    return kernel\n"
        )
        keys = [k for k, _l in jitted_kernels(ast.parse(src), "fake")]
        assert keys == ["fake.factory.kernel"]

    def test_stale_registry_key_fires(self):
        rule = OracleTwinRule()
        ctx = _ops_ctx("ORACLE_TWINS = {}\n", "kubernetes_tpu/ops/parity.py")
        findings = rule._check_registry(
            ctx,
            {"solver.kernel_that_never_existed": {
                "oracle": "ops.oracle.solve_sequential_numpy",
                "suite": "tests/test_solver_parity.py"}},
            {"solver.kernel_that_never_existed": 1},
        )
        assert findings and "stale" in findings[0].message

    def test_unresolvable_oracle_fires(self):
        rule = OracleTwinRule()
        ctx = _ops_ctx("ORACLE_TWINS = {}\n", "kubernetes_tpu/ops/parity.py")
        findings = rule._check_registry(
            ctx,
            {"solver._solve_xla": {
                "oracle": "ops.oracle.no_such_twin",
                "suite": "tests/test_solver_parity.py"}},
            {"solver._solve_xla": 1},
        )
        assert findings and "does not resolve" in findings[0].message

    def test_oracle_resolution_helper(self):
        assert resolve_oracle("ops.oracle.solve_sequential_numpy")
        assert resolve_oracle("scheduler.gang.member_counts_host")
        assert resolve_oracle("ops.oracle.nope_nope") is None

    def test_registry_resolves_at_runtime(self):
        """Static strings stay honest: every oracle imports, every
        kernel key's module + top-level symbol exist."""
        import importlib

        from kubernetes_tpu.ops.parity import ORACLE_TWINS

        assert ORACLE_TWINS, "registry must not be empty"
        for key, entry in ORACLE_TWINS.items():
            mod_name, rest = key.split(".", 1)
            mod = importlib.import_module(f"kubernetes_tpu.ops.{mod_name}")
            top = rest.split(".", 1)[0]
            assert hasattr(mod, top), f"{key}: {top} missing in ops/{mod_name}"
            omod_path, ofunc = entry["oracle"].rsplit(".", 1)
            omod = importlib.import_module(
                f"kubernetes_tpu.{omod_path}"
                if not omod_path.startswith("tests") else omod_path
            )
            assert callable(getattr(omod, ofunc)), entry["oracle"]
            assert os.path.exists(os.path.join(ROOT, entry["suite"]))


# -- live-tree gates (the acceptance criterion) -------------------------


class TestLiveTree:
    def test_lock_graph_clean_on_live_tree(self):
        """Zero lock-order cycles, zero interprocedural *_locked
        violations on kubernetes_tpu/ — ktsan's static baseline is
        EMPTY and must stay empty (pragma with a reason, or fix)."""
        rep = lockgraph.analyze()
        assert rep.cycles == [], rep.render()
        assert rep.violations == [], rep.render()
        # It audited real code: locks inventoried, edges extracted,
        # and the one documented pragma grant is visible.
        assert len(rep.locks) >= 20
        assert rep.edges, "no ordering edges extracted?"
        assert rep.suppressed >= 1

    def test_lock_graph_cli(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.ktlint", "--lock-graph",
             "--format=json"],
            capture_output=True, text=True, timeout=120, cwd=ROOT,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        data = json.loads(proc.stdout)
        assert data["cycles"] == [] and data["violations"] == []
        assert data["counts"]["KTSAN01"] == 0

    def test_kt006_clean_on_live_tree(self):
        from tools import ktlint

        rep = ktlint.lint(select=["KT006"], baseline_path=None)
        assert rep.findings == [], [f.render() for f in rep.findings]


# -- scatter twin parity ------------------------------------------------


def test_scatter_rows_parity():
    import jax.numpy as jnp

    from kubernetes_tpu.ops.incremental import _scatter_rows
    from kubernetes_tpu.ops.oracle import scatter_rows_numpy

    rng = np.random.default_rng(0)
    host = {
        "a": rng.standard_normal((16, 4)).astype(np.float32),
        "b": rng.integers(0, 100, size=16).astype(np.int32),
    }
    idx = np.array([3, 7, 11], np.int32)
    rows = {
        "a": rng.standard_normal((3, 4)).astype(np.float32),
        "b": rng.integers(0, 100, size=3).astype(np.int32),
    }
    want = scatter_rows_numpy(host, idx, rows)
    got = _scatter_rows(
        {k: jnp.asarray(v) for k, v in host.items()},
        jnp.asarray(idx),
        {k: jnp.asarray(v) for k, v in rows.items()},
    )
    for k in host:
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])


def test_scatter_rows_banded_parity():
    import jax.numpy as jnp

    from kubernetes_tpu.ops.oracle import scatter_rows_banded_numpy
    from kubernetes_tpu.ops.pallas_scan import scatter_rows_banded

    rng = np.random.default_rng(1)
    counts = rng.integers(0, 110, size=(16, 128)).astype(np.int16)
    svc = rng.integers(0, 16, size=8).astype(np.int32)
    slot = rng.integers(0, 128, size=8).astype(np.int32)
    # The session pads by repeating its last entry: the same write twice.
    svc[-1], slot[-1] = svc[-2], slot[-2]
    value = rng.integers(0, 110, size=8).astype(np.int16)
    value[-1] = value[-2]
    want = scatter_rows_banded_numpy(counts, svc, slot, value)
    got = scatter_rows_banded(
        jnp.asarray(counts), jnp.asarray(svc), jnp.asarray(slot),
        jnp.asarray(value),
    )
    np.testing.assert_array_equal(np.asarray(got), want)


# -- recompilation sentinel ---------------------------------------------


class TestRecompilationSentinel:
    def test_bounded_compiles_across_randomized_backlogs(self):
        """The pow2/static-bucketing contract, asserted where it
        bites: N randomized backlog shapes must funnel into a handful
        of padded shapes, and the solver must compile AT MOST once per
        padded shape (jit cache-size delta). A bucketing regression
        (padding by exact size, a dtype wobble, a non-static arg)
        fails this immediately instead of as a mystery slowdown."""
        import random

        import jax

        from kubernetes_tpu.models.columnar import build_snapshot
        from kubernetes_tpu.ops import device_snapshot, solve_assignments
        from kubernetes_tpu.ops.solver import _solve_xla
        from test_solver_parity import mk_node, mk_pod

        jax.clear_caches()
        assert _solve_xla._cache_size() == 0
        rng = random.Random(0xA11CE)
        padded_shapes = set()
        runs = 0
        for _ in range(10):
            P = rng.randint(1, 600)
            N = rng.randint(1, 40)
            pods = [
                mk_pod(f"p{i}", cpu=rng.choice([50, 100, 250]))
                for i in range(P)
            ]
            nodes = [mk_node(f"n{j}") for j in range(N)]
            snap = build_snapshot(pods, nodes)
            d = device_snapshot(snap)
            out = solve_assignments(d)
            assert len(out) == P
            padded_shapes.add(
                (d.pods["cpu"].shape[0], d.nodes["cpu_cap"].shape[0])
            )
            runs += 1
        # Bucketing must coalesce: 10 random shapes, few padded ones.
        assert len(padded_shapes) < runs
        assert len(padded_shapes) <= 4  # pow2 buckets for P<=600, N<=40
        compiles = _solve_xla._cache_size()
        assert compiles <= len(padded_shapes), (
            f"{compiles} compiles for {len(padded_shapes)} padded shapes "
            f"({sorted(padded_shapes)}) — shape bucketing regressed"
        )
