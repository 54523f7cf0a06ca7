"""hyperkube: every daemon in one binary, dispatched on argv[1].

Reference: cmd/hyperkube/main.go:34-38 (hk.AddServer for apiserver,
controller-manager, scheduler, kubelet, proxy) — plus ktctl and the
local-up-cluster composition for parity with hack/local-up-cluster.sh.

Usage:
    python -m kubernetes_tpu.cmd.hyperkube <server> [flags...]
    servers: apiserver, controller-manager, scheduler, kubelet, proxy,
             ktctl, local-up-cluster
"""

from __future__ import annotations

import sys
from typing import List, Optional

from kubernetes_tpu.cmd import daemons

SERVERS = {
    "apiserver": daemons.apiserver_main,
    "controller-manager": daemons.controller_manager_main,
    "scheduler": daemons.scheduler_main,
    "kubelet": daemons.kubelet_main,
    "proxy": daemons.proxy_main,
}


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        names = sorted(SERVERS) + ["ktctl", "local-up-cluster"]
        print(f"usage: hyperkube <server> [flags]\nservers: {', '.join(names)}")
        return 0 if argv else 1
    name, rest = argv[0], argv[1:]
    from kubernetes_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    if name == "ktctl":
        from kubernetes_tpu.cli.ktctl import main as ktctl_main

        return ktctl_main(rest)
    if name == "local-up-cluster":
        from kubernetes_tpu.cmd.localup import main as localup_main

        return localup_main(rest)
    fn = SERVERS.get(name)
    if fn is None:
        print(f"error: unknown server {name!r}", file=sys.stderr)
        return 1
    return fn(rest)


if __name__ == "__main__":
    sys.exit(main())
