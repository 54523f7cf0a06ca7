"""Cloud provider layer tests (reference behaviors:
pkg/cloudprovider/, nodecontroller sync)."""

import jax
import pytest

from kubernetes_tpu.client.rest import Client, LocalTransport
from kubernetes_tpu.cloudprovider import (
    FakeCloudProvider,
    Instance,
    TPUCloudProvider,
    Zone,
    get_provider,
    register_provider,
)
from kubernetes_tpu.cloudprovider.tpu import (
    LABEL_CHIP,
    LABEL_CHIPS,
    LABEL_HOST,
    LABEL_PLATFORM,
)
from kubernetes_tpu.controllers.cloudnodes import (
    LABEL_MANAGED,
    LABEL_ZONE,
    CloudNodeController,
)
from kubernetes_tpu.server.api import APIServer


class TestRegistry:
    def test_builtin_providers_registered(self):
        assert isinstance(get_provider("fake"), FakeCloudProvider)
        # The TPU provider opens this host's TPU; on a host where it
        # cannot (none here, or one held by a --batch scheduler) it
        # refuses by name instead of describing the CPU backend.
        with pytest.raises(SystemExit, match="cloud-provider tpu"):
            get_provider("tpu")

    def test_unknown_provider(self):
        with pytest.raises(KeyError):
            get_provider("no-such-cloud")

    def test_custom_registration(self):
        register_provider("custom", lambda: FakeCloudProvider())
        assert isinstance(get_provider("custom"), FakeCloudProvider)


class TestTPUProvider:
    def test_discovers_hosts_from_devices(self):
        # conftest forces 8 virtual CPU devices in one process = 1 host.
        provider = TPUCloudProvider(devices=jax.devices())
        instances = provider.instances()
        assert len(instances) == 1
        inst = instances[0]
        assert inst.name == "tpu-host-0"
        labels = inst.labels_dict()
        assert labels[LABEL_CHIPS] == str(len(jax.devices()))
        assert labels[LABEL_HOST] == "0"
        assert LABEL_PLATFORM in labels and LABEL_CHIP in labels

    def test_zone_is_slice_scoped(self):
        provider = TPUCloudProvider(
            devices=jax.devices(), slice_name="slice-A"
        )
        zone = provider.zone_of("tpu-host-0")
        assert zone == Zone(failure_domain="slice-A/host-0", region="slice-A")
        assert provider.zone_of("nope") is None
        assert provider.cluster_names() == ["slice-A"]

    def test_multi_host_ring_routes(self):
        class Dev:
            def __init__(self, pid):
                self.process_index = pid
                self.device_kind = "TPU v5e"
                self.platform = "tpu"

        devices = [Dev(p) for p in (0, 0, 1, 1, 2, 2)]
        provider = TPUCloudProvider(devices=devices)
        instances = provider.instances()
        assert [i.name for i in instances] == [
            "tpu-host-0", "tpu-host-1", "tpu-host-2",
        ]
        assert instances[0].instance_type == "tpu-2x-TPU-v5e"
        routes = provider.routes()
        targets = {r.target_instance for r in routes}
        assert targets == {"tpu-host-0", "tpu-host-1", "tpu-host-2"}
        assert len(routes) == 3  # ring with wraparound


class TestCloudNodeController:
    def setup_method(self):
        self.api = APIServer()
        self.client = Client(LocalTransport(self.api))

    def test_registers_and_labels_nodes(self):
        provider = FakeCloudProvider(
            instances=[
                Instance(
                    name="host-a",
                    instance_type="tpu-4x",
                    labels=(("chip", "v5e"),),
                )
            ],
            zones={"host-a": Zone(failure_domain="s0/h0", region="s0")},
        )
        ctl = CloudNodeController(self.client, provider)
        assert ctl.sync_once() == 1
        node = self.client.get("nodes", "host-a")
        assert node.metadata.labels[LABEL_MANAGED] == "cloud"
        assert node.metadata.labels[LABEL_ZONE] == "s0_h0"
        assert node.metadata.labels["chip"] == "v5e"
        assert node.status.conditions[0].status == "Unknown"
        # Second pass: nothing to do.
        assert ctl.sync_once() == 0

    def test_reaps_only_cloud_managed_nodes(self):
        provider = FakeCloudProvider(instances=[Instance(name="host-a")])
        ctl = CloudNodeController(self.client, provider)
        ctl.sync_once()
        # A self-registered (kubelet) node the cloud doesn't know about:
        self.api.create(
            "nodes", "",
            {"kind": "Node", "metadata": {"name": "manual-node"}},
        )
        provider.set_instances([])  # host-a left the slice
        changed = ctl.sync_once()
        assert changed == 1
        names = {n.metadata.name for n in self.client.list("nodes")[0]}
        assert names == {"manual-node"}  # cloud node gone, manual kept

    def test_tpu_provider_end_to_end(self):
        ctl = CloudNodeController(
            self.client, TPUCloudProvider(devices=jax.devices())
        )
        assert ctl.sync_once() == 1
        node = self.client.get("nodes", "tpu-host-0")
        assert node.metadata.labels[LABEL_MANAGED] == "cloud"
        assert LABEL_CHIPS in node.metadata.labels
