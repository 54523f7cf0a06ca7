"""TPU fabric provider: the accelerator pod IS the cloud.

Reference seam: pkg/cloudprovider/gce/gce.go et al. discover VM
instances from a cloud API; here the equivalent inventory — hosts,
chips, ICI links — comes from JAX's view of the TPU slice
(jax.devices(): process_index = host, coords = position in the
physical torus, device_kind = chip generation).

One INSTANCE per host (a host runs one kubelet/node agent and owns its
local chips); chip inventory and torus coordinates surface as instance
labels so the scheduler can use them as nodeSelector targets, exactly
how cloud zone/instance-type labels are used in the reference.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from kubernetes_tpu.cloudprovider.interface import (
    CloudProvider,
    Instance,
    LoadBalancerStub,
    Route,
    Zone,
    register_provider,
)

# Node label keys (the reference-era equivalents were
# failure-domain.beta.kubernetes.io/zone etc.).
LABEL_PLATFORM = "tpu.kubernetes-tpu.io/platform"
LABEL_CHIP = "tpu.kubernetes-tpu.io/chip"
LABEL_CHIPS = "tpu.kubernetes-tpu.io/chips-per-host"
LABEL_HOST = "tpu.kubernetes-tpu.io/host-index"
LABEL_COORDS = "tpu.kubernetes-tpu.io/coords"


def _local_tpu_devices() -> list:
    """This host's TPU devices. Opening them loads libtpu, which one
    process per host may hold (JAX offers no device list without
    loading it): with a --batch scheduler on this host, it is that
    scheduler's. Refuse with the reason rather than hang or describe
    the CPU backend."""
    import jax

    try:
        return jax.devices("tpu")
    except RuntimeError as e:
        raise SystemExit(
            "--cloud-provider tpu: cannot open this host's TPU, which "
            "one process at a time may hold — a --batch scheduler on "
            "this host has it, or the host has none. Run the "
            "controller-manager with --cloud-provider tpu on a host "
            f"whose chips are free, or without it. ({e})"
        ) from e


class TPUCloudProvider(CloudProvider):
    name = "tpu"

    def __init__(self, devices=None, slice_name: str = "slice-0"):
        if devices is None:
            devices = _local_tpu_devices()
        self.devices = list(devices)
        self.slice_name = slice_name
        # Managed routes (RouteController's pod-CIDR routes) layered on
        # top of the discovered ICI base ring.
        self._managed_routes: Dict[str, Route] = {}
        # Fabric ingress surface: portal rules at the slice edge.
        self._lb = LoadBalancerStub()

    # -- host grouping ------------------------------------------------

    def _hosts(self) -> Dict[int, List]:
        hosts: Dict[int, List] = {}
        for d in self.devices:
            hosts.setdefault(int(getattr(d, "process_index", 0)), []).append(d)
        return hosts

    @staticmethod
    def _coords(device) -> Optional[tuple]:
        coords = getattr(device, "coords", None)
        return tuple(coords) if coords is not None else None

    def host_name(self, process_index: int) -> str:
        return f"tpu-host-{process_index}"

    # -- CloudProvider ------------------------------------------------

    def instances(self) -> List[Instance]:
        out = []
        hosts = self._hosts()
        for pid, devs in sorted(hosts.items()):
            kind = getattr(devs[0], "device_kind", "unknown")
            platform = getattr(devs[0], "platform", "tpu")
            coords = [c for c in (self._coords(d) for d in devs) if c]
            labels = {
                LABEL_PLATFORM: str(platform),
                LABEL_CHIP: str(kind).replace(" ", "-"),
                LABEL_CHIPS: str(len(devs)),
                LABEL_HOST: str(pid),
            }
            if coords:
                # Label-value safe encoding (no commas/semicolons pass
                # validation): chip coords dash-joined, chips dot-joined
                # -> "0-0-0.1-0-0".
                labels[LABEL_COORDS] = ".".join(
                    "-".join(str(x) for x in c) for c in sorted(coords)
                )
            out.append(
                Instance(
                    name=self.host_name(pid),
                    addresses=("127.0.0.1",) if len(hosts) == 1 else (),
                    instance_type=f"{platform}-{len(devs)}x-{str(kind).replace(' ', '-')}",
                    instance_id=f"{self.slice_name}/host-{pid}",
                    labels=tuple(sorted(labels.items())),
                )
            )
        return out

    def zone_of(self, instance_name: str) -> Optional[Zone]:
        for pid in self._hosts():
            if self.host_name(pid) == instance_name:
                return Zone(
                    failure_domain=f"{self.slice_name}/host-{pid}",
                    region=self.slice_name,
                )
        return None

    def _base_routes(self) -> List[Route]:
        """ICI connectivity between hosts, modeled as a ring over host
        indices — the wraparound links every host has on real torus
        slices. (Finer-grained coords-based adjacency would refine
        this; the ring is what consumers can rely on today.)"""
        hosts = sorted(self._hosts())
        if len(hosts) <= 1:
            return []
        out = []
        for i, pid in enumerate(hosts):
            nxt = hosts[(i + 1) % len(hosts)]
            out.append(
                Route(
                    name=f"ici-{pid}-{nxt}",
                    target_instance=self.host_name(nxt),
                    destination_cidr=f"host://{nxt}",
                )
            )
        return out

    def routes(self) -> List[Route]:
        return self._base_routes() + list(self._managed_routes.values())

    def create_route(
        self, name: str, target_instance: str, destination_cidr: str
    ) -> None:
        self._managed_routes[name] = Route(
            name=name,
            target_instance=target_instance,
            destination_cidr=destination_cidr,
        )

    def delete_route(self, name: str) -> None:
        self._managed_routes.pop(name, None)

    def load_balancer(self) -> LoadBalancerStub:
        return self._lb

    def cluster_names(self) -> List[str]:
        return [self.slice_name]


register_provider("tpu", TPUCloudProvider)
