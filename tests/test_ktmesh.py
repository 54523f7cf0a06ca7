"""ktmesh — static SPMD partitioning analyzer tests.

Four layers, mirroring the pass itself:

- KT009 fixtures: the AST half (mesh hygiene in ops/) on violating /
  passing / pragma'd snippets.
- Contract-surface units: symbolic PartitionSpecs, the HLO collective
  inventory walker, and the runtime COMM verdict — pure functions, no
  lowering.
- Drift injection: doctored contracts through the real partitioned
  lowering (tightened budget, phantom declared kind, replication that
  vanishes a declared collective, a deliberately mis-sharded wave
  solver that full-gathers the pod axis, coupling-class lies).
- The gates: the live tree must analyze clean in-process on conftest's
  8 forced devices, the CLI must round-trip JSON, and <2 devices must
  degrade to 'skipped' + exit 0 in a subprocess.
"""

import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from kubernetes_tpu.ops import contracts as C
from tools import ktlint
from tools.ktlint import ktmesh

pytestmark = pytest.mark.ktmesh

REPO = pathlib.Path(__file__).resolve().parent.parent

GANG = "matrices.gang_member_counts"


def _resharded(name, **changes):
    """CONTRACTS[name] with its sharding leaf fields replaced."""
    c = C.CONTRACTS[name]
    return dataclasses.replace(
        c, sharding=dataclasses.replace(c.sharding, **changes)
    )


def _check(name, contract):
    meta = {}
    findings = ktmesh.check_kernel(name, contract, 8, meta=meta)
    return findings, meta


# -- KT009: the AST half ------------------------------------------------


def _lint(tmp_path, source, filename="mod.py"):
    opsdir = tmp_path / "ops"
    opsdir.mkdir(exist_ok=True)
    f = opsdir / filename
    f.write_text(textwrap.dedent(source))
    return ktlint.lint([f], select=["KT009"], baseline_path=None)


class TestKT009Fixtures:
    def test_device_put_without_sharding_flagged(self, tmp_path):
        report = _lint(
            tmp_path,
            """
            import jax

            def stage(x):
                return jax.device_put(x)
            """,
        )
        assert len(report.findings) == 1
        assert report.findings[0].rule == "KT009"
        assert "device_put" in report.findings[0].message

    def test_device_put_with_placement_passes(self, tmp_path):
        report = _lint(
            tmp_path,
            """
            import jax

            def stage(x, sharding, dev):
                a = jax.device_put(x, sharding)
                b = jax.device_put(x, sharding=sharding)
                c = jax.device_put(x, device=dev)
                return a, b, c
            """,
        )
        assert report.findings == []

    def test_devices_indexing_flagged(self, tmp_path):
        report = _lint(
            tmp_path,
            """
            import jax

            def first():
                return jax.devices()[0]

            def window():
                return jax.local_devices()[:4]
            """,
        )
        assert len(report.findings) == 2
        assert all("topology" in f.message for f in report.findings)

    def test_pmap_flagged(self, tmp_path):
        report = _lint(
            tmp_path,
            """
            import jax

            def build(f):
                return jax.pmap(f)
            """,
        )
        assert len(report.findings) == 1
        assert "pmap" in report.findings[0].message

    def test_mesh_construction_outside_seam_flagged(self, tmp_path):
        report = _lint(
            tmp_path,
            """
            import jax
            import numpy as np
            from jax.sharding import Mesh

            def ad_hoc():
                a = Mesh(np.array(jax.devices()), ("nodes",))
                b = jax.sharding.Mesh(np.array(jax.devices()), ("x",))
                return a, b
            """,
        )
        assert len(report.findings) == 2
        assert all("seam" in f.message for f in report.findings)

    def test_mesh_construction_in_matrices_seam_passes(self, tmp_path):
        report = _lint(
            tmp_path,
            """
            import jax
            import numpy as np
            from jax.sharding import Mesh

            def host_mesh(n):
                return Mesh(np.array(jax.devices()), ("nodes",))
            """,
            filename="matrices.py",
        )
        assert report.findings == []

    def test_pragma_suppresses(self, tmp_path):
        report = _lint(
            tmp_path,
            """
            import jax

            def first():
                # ktlint: disable=KT009
                return jax.devices()[0]
            """,
        )
        assert report.findings == []
        assert len(report.suppressed) == 1

    def test_out_of_scope_module_ignored(self, tmp_path):
        pkg = tmp_path / "controllers"
        pkg.mkdir()
        f = pkg / "mod.py"
        f.write_text("import jax\nd = jax.devices()[0]\n")
        report = ktlint.lint([f], select=["KT009"], baseline_path=None)
        assert report.findings == []

    def test_live_tree_kt009_clean(self):
        report = ktlint.lint(select=["KT009"])
        assert report.findings == [], [f.render() for f in report.findings]


# -- contract-surface units --------------------------------------------


class TestPartitionSpecs:
    def test_leaf_spec_shards_only_the_declared_dim(self):
        leaf = C.ArraySpec(("N", "S"), "f32")
        sh = C.MeshSharding(dim="N", axis="nodes")
        assert C.partition_spec(leaf, sh) == ("nodes", None)
        assert C.partition_spec(C.ArraySpec(("P",), "f32"), sh) == (None,)

    def test_solver_specs_node_shard_nodes_replicate_pods(self):
        specs = C.partition_specs(C.CONTRACTS["solver._solve_xla"])
        assert specs["args"]["nodes"]["cpu_cap"] == ("nodes",)
        assert specs["args"]["nodes"]["svc_counts"] == ("nodes", None)
        assert specs["args"]["pods"]["cpu"] == (None,)
        assert specs["args"]["weights"] is None  # static
        assert specs["results"] == (None,)  # i32[P], replicated

    def test_every_contract_exposes_specs(self):
        for name, contract in C.CONTRACTS.items():
            specs = C.partition_specs(contract)
            assert set(specs) == {"args", "results"}, name


class TestCollectiveInventory:
    HLO = textwrap.dedent(
        """
        ENTRY %main {
          %p = f32[384,1]{1,0} parameter(0)
          %ag = f32[384,8]{1,0} all-gather(f32[384,1]{1,0} %p), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={1}
          %q = f32[256]{0} parameter(1)
          %ar = f32[256]{0} all-reduce(f32[256]{0} %q), to_apply=%add
          %b = pred[40]{0} parameter(2)
          %ar2 = pred[40]{0} all-reduce(pred[40]{0} %b), to_apply=%or
          %plain = f32[256]{0} add(f32[256]{0} %q, f32[256]{0} %q)
        }
        """
    )

    def test_counts_bytes_and_gather_dim(self):
        inv = C.collective_inventory(self.HLO)
        assert inv["counts"] == {"all-gather": 1, "all-reduce": 2}
        assert inv["total"] == 3
        # f32[384,8] = 12288 B; f32[256] = 1024 B + pred[40] = 40 B.
        assert inv["bytes"] == {"all-gather": 12288, "all-reduce": 1064}
        ag = [op for op in inv["ops"] if op["kind"] == "all-gather"][0]
        assert ag["gather_dim"] == 1
        assert ag["shape"] == [384, 8]

    def test_combined_tuple_collective_counts_each_element(self):
        # XLA's combiner (JAX 0.9) fuses independent all-reduces into
        # one tuple-result op; the inventory still names each of them.
        inv = C.collective_inventory(
            "%all-reduce.6 = (s32[32]{0}, s32[32]{0}, s32[], s32[], "
            "s32[], /*index=5*/s32[]) all-reduce(%a, %b, %c, %d, %e, "
            "%f), channel_id=1, to_apply=%add"
        )
        assert inv["counts"] == {"all-reduce": 6}
        assert inv["bytes"] == {"all-reduce": 2 * 128 + 4 * 4}

    def test_collective_free_module(self):
        inv = C.collective_inventory("%x = f32[8]{0} add(%a, %b)")
        assert inv == {"counts": {}, "bytes": {}, "total": 0, "ops": []}


class TestCommVerdict:
    def test_unknown_kernel_is_uncontracted(self):
        assert C.comm_verdict("nope.missing", {"all-reduce": 1}) == (
            "uncontracted"
        )

    def test_empty_inventory_is_ok(self):
        assert C.comm_verdict(GANG, {}) == "ok"

    def test_declared_kinds_any_count_ok(self):
        # Count-lenient: runtime buckets differ from the probe point.
        assert C.comm_verdict(GANG, {"all-reduce": 7}) == "ok"

    def test_undeclared_kind_is_drift(self):
        v = C.comm_verdict(GANG, {"all-reduce": 1, "all-gather": 2})
        assert v == "drift: undeclared all-gather"


# -- drift injection through the real lowering -------------------------


class TestDriftInjection:
    def test_pristine_contract_is_clean(self):
        findings, meta = _check(GANG, C.CONTRACTS[GANG])
        assert findings == []
        assert meta["status"] == "ok"
        assert meta["collectives"] == {"all-reduce": 1}

    def test_tightened_budget_is_finding(self):
        bad = _resharded(GANG, budget=C.CommBudget(all_reduce=2))
        findings, _ = _check(GANG, bad)
        assert [f.check for f in findings] == ["budget"]

    def test_phantom_declared_kind_is_finding(self):
        bad = _resharded(
            GANG, budget=C.CommBudget(all_reduce=1, collective_permute=3)
        )
        findings, _ = _check(GANG, bad)
        assert [f.check for f in findings] == ["budget"]

    def test_replication_vanishing_declared_collective_is_finding(self):
        # Mis-sharded leaf: full replication lowers collective-free,
        # contradicting the declared all_reduce=1.
        bad = _resharded(GANG, dim=None)
        findings, meta = _check(GANG, bad)
        assert meta["collectives"] == {}
        assert "budget" in [f.check for f in findings]

    def test_pod_sharded_wave_full_gathers_pod_axis(self):
        # The deliberately mis-sharded fixture kernel: wave couples
        # pods through windowed commits, so pod-axis sharding makes
        # GSPMD materialize the FULL pod axis — exactly the silent
        # scaling loss the pod-gather check exists for.
        bad = _resharded(
            "wave.solve_waves", dim="P", axis="pods",
            budget=C.CommBudget(),
        )
        findings, meta = _check("wave.solve_waves", bad)
        checks = {f.check for f in findings}
        assert "pod-gather" in checks
        assert "budget" in checks
        pod = [f for f in findings if f.check == "pod-gather"]
        assert any("P=384" in f.message for f in pod)

    def test_shardable_with_collectives_fails_coupling_xcheck(self):
        # Lie about the coupling class: gang reduces over the pod axis
        # (one psum), so claiming 'shardable' must trip the cross-check
        # even though the budget itself matches.
        c = _resharded(GANG)  # pristine sharding
        bad = dataclasses.replace(c, pod_axis="shardable")
        findings, _ = _check(GANG, bad)
        assert [f.check for f in findings] == ["coupling-xcheck"]

    def test_reduces_with_empty_inventory_fails_coupling_xcheck(self):
        # explain_rows is genuinely collective-free under pod sharding;
        # claiming it 'reduces' contradicts that.
        c = C.CONTRACTS["solver.explain_rows"]
        bad = dataclasses.replace(c, pod_axis="reduces")
        findings, _ = _check("solver.explain_rows", bad)
        assert [f.check for f in findings] == ["coupling-xcheck"]

    def test_missing_sharding_leaf_is_completeness_finding(self):
        bad = dataclasses.replace(C.CONTRACTS[GANG], sharding=None)
        findings, meta = _check(GANG, bad)
        assert [f.check for f in findings] == ["completeness"]
        assert meta["status"] == "error"

    def test_bogus_axis_is_completeness_finding(self):
        bad = _resharded(GANG, axis="rings")
        findings, _ = _check(GANG, bad)
        assert [f.check for f in findings] == ["completeness"]

    def test_unknown_dim_is_completeness_finding(self):
        bad = _resharded(GANG, dim="ZZ")
        findings, _ = _check(GANG, bad)
        assert [f.check for f in findings] == ["completeness"]

    def test_analyze_surfaces_drift_and_fails(self, monkeypatch):
        monkeypatch.setitem(
            C.CONTRACTS, GANG,
            _resharded(GANG, budget=C.CommBudget(all_reduce=2)),
        )
        report = ktmesh.analyze(devices=8, kernels=[GANG])
        assert report.exit_code == 1
        assert [f.check for f in report.findings] == ["budget"]


# -- the runtime join: ledger COMM verdict ------------------------------


class TestRuntimeCommVerdict:
    def _dispatch(self):
        import jax.numpy as jnp

        from kubernetes_tpu.ops import ledger
        from kubernetes_tpu.ops.matrices import gang_member_counts

        out = gang_member_counts(
            jnp.ones(16, dtype=bool), jnp.zeros(16, dtype=jnp.int32), 8
        )
        out.block_until_ready()
        assert ledger.DEFAULT.wait_pending(60)
        return ledger

    def test_ledger_rows_carry_collective_inventory(self):
        ledger = self._dispatch()
        rows = {r["kernel"]: r for r in ledger.DEFAULT.rows()}
        shapes = rows[GANG]["shapes"]
        # Unsharded dispatch: empty inventory, verdict trivially ok.
        assert any(
            s.get("collectives") == {}
            and s.get("collectives_verdict") == "ok"
            for s in shapes
        ), [(s["signature"], s.get("collectives_verdict")) for s in shapes]

    def test_ktctl_profile_renders_comm_column(self, capsys):
        from kubernetes_tpu.cli import ktctl
        from kubernetes_tpu.client import Client, LocalTransport
        from kubernetes_tpu.server.api import APIServer

        self._dispatch()
        rc = ktctl.main(
            ["profile", "kernels"],
            client=Client(LocalTransport(APIServer())),
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "COMM" in out
        gang_line = [ln for ln in out.splitlines() if GANG in ln][0]
        assert gang_line.rstrip().endswith("ok")


# -- the gates ----------------------------------------------------------


class TestLiveTreeGate:
    def test_live_tree_analyzes_clean(self):
        report = ktmesh.analyze(devices=8)
        assert report.errors == []
        assert report.findings == [], [
            f.render() for f in report.findings
        ]
        assert report.exit_code == 0
        assert len(report.kernels) == len(C.CONTRACTS)
        assert all(k["status"] == "ok" for k in report.kernels)
        # The budgets are evidence, not decoration: the node-sharded
        # solvers DO communicate, and explain_rows does NOT.
        assert report.collectives_total > 0
        by_name = {k["kernel"]: k for k in report.kernels}
        assert by_name["solver.explain_rows"]["collectives"] == {}
        assert by_name["solver._solve_xla"]["collectives_total"] > 0

    def test_to_json_schema(self):
        report = ktmesh.analyze(devices=8, kernels=[GANG])
        data = report.to_json()
        assert set(data) == {
            "devices", "kernels_checked", "kernels", "collectives_total",
            "collective_bytes_total", "skipped", "findings", "errors",
        }
        assert data["kernels_checked"] == 1
        assert data["kernels"][0]["budget"] == {"all-reduce": 1}


class TestCLI:
    def test_single_kernel_json_roundtrip(self, mesh_subprocess_env):
        proc = subprocess.run(
            [
                sys.executable, "-m", "tools.ktlint", "--mesh-analysis",
                "--format=json", GANG,
            ],
            cwd=REPO, env=mesh_subprocess_env,
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr
        data = json.loads(proc.stdout)
        assert data["kernels_checked"] == 1
        assert data["kernels"][0]["status"] == "ok"
        assert data["findings"] == []

    def test_unknown_kernel_key_is_usage_error(self, mesh_subprocess_env):
        proc = subprocess.run(
            [
                sys.executable, "-m", "tools.ktlint", "--mesh-analysis",
                "kubernetes_tpu/ops/solver.py",
            ],
            cwd=REPO, env=mesh_subprocess_env,
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 2
        assert "kernel keys" in proc.stderr

    def test_off_mesh_degrades_to_skipped_exit_zero(self):
        # A host without the forced multi-device platform cannot add
        # evidence but must not fail CI: every kernel 'skipped', exit 0.
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [
                sys.executable, "-m", "tools.ktlint", "--mesh-analysis",
                "--devices", "1", "--format=json",
            ],
            cwd=REPO, env=env,
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr
        data = json.loads(proc.stdout)
        assert data["devices"] == 1
        assert data["kernels_checked"] == len(C.CONTRACTS)
        assert data["skipped"] == len(C.CONTRACTS)
        assert all(
            k["status"] == "skipped" and "skip_reason" in k
            for k in data["kernels"]
        )
        assert data["findings"] == []
