"""Process-based container runtime: pods are real OS processes.

The TPU-native analog of the reference's DockerManager
(pkg/kubelet/dockertools/manager.go:1201-1315): each pod starts an
infra anchor — the native `pause` binary (native/pause.c, equivalent of
third_party/pause/pause.asm) — then one subprocess per container.
Containers are compared by a hash of their runtime-relevant spec
(computePodContainerChanges' hash check, manager.go:1287+): a changed
spec kills and recreates the container with an incremented restart
count. stdout/stderr stream to per-container log files — the substrate
for the kubelet's /logs endpoint and `ktctl logs`.

"Image" semantics: a process runtime has no registry; the container's
`command` + `args` are the process. A container without a command runs
the pause binary (a well-behaved forever-process), which keeps
reference manifests (image-only nginx pods) runnable in integration
tests.

Restart-crossing state: each container writes a JSON record
(pid, spec hash, restart count, log path) under
<root>/pods/<uid>/<name>.json. A restarted kubelet's runtime ADOPTS
live recorded processes instead of orphaning them — the reference
reconstructs the same way from `docker ps` (kubelet.go:1154-1160).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import signal
import subprocess
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from kubernetes_tpu.models.objects import Pod
from kubernetes_tpu.kubelet.runtime import ContainerRuntime, RuntimeContainer


def _spec_hash(spec) -> str:
    ident = json.dumps(
        {
            "image": spec.image,
            "command": spec.command,
            "args": spec.args,
            "env": [(e.name, e.value) for e in spec.env],
            "workingDir": spec.working_dir,
        },
        sort_keys=True,
    )
    return hashlib.sha1(ident.encode()).hexdigest()[:16]


@dataclass
class _Proc:
    """One live (or exited) container process."""

    pid: int
    popen: Optional[subprocess.Popen]  # None for adopted processes
    spec_hash: str
    name: str
    image: str
    log_path: str
    restart_count: int = 0
    started_at: float = 0.0
    exit_code: Optional[int] = None  # None while running

    def poll(self) -> Optional[int]:
        if self.exit_code is not None:
            return self.exit_code
        if self.popen is not None:
            rc = self.popen.poll()
            if rc is not None:
                self.exit_code = rc
            return self.exit_code
        # Adopted process: liveness via /proc; exit code unknowable.
        try:
            os.kill(self.pid, 0)
            return None
        except OSError:
            self.exit_code = 0
            return 0


class ProcessRuntime(ContainerRuntime):
    """Real-process runtime rooted at `root_dir` (logs + pod records)."""

    # Containers run with host networking: servers they start listen on
    # the host's loopback, so the kubelet reports that as the pod IP
    # (reference HostNetwork semantics).
    host_network_ip = "127.0.0.1"

    def __init__(self, root_dir: str, node_name: str = ""):
        self.root = root_dir
        self.node_name = node_name
        os.makedirs(os.path.join(self.root, "pods"), exist_ok=True)
        self._lock = threading.RLock()
        self._pods: Dict[str, Dict[str, _Proc]] = {}
        self._anchors: Dict[str, _Proc] = {}
        # Per-NAMESPACE service env injected into containers (the
        # kubelet keeps this current from its service informer;
        # reference: pkg/kubelet/envvars FromServices, filtered to the
        # pod's namespace by getServiceEnvVarMap). Captured at
        # container START, like the reference — service churn does not
        # restart running containers.
        self.service_env: Dict[str, Dict[str, str]] = {}
        # Cluster DNS surface (kubelet --cluster-dns/--cluster-domain;
        # the reference writes these into pod resolv.conf, here they
        # reach apps as env).
        self.cluster_dns: str = ""
        self.cluster_domain: str = "cluster.local"
        # "uid/name" -> restart count to apply at next (re)start; set
        # by restart_container, consumed by sync_pod.
        self._restart_counts: Dict[str, int] = {}
        self._adopt_existing()

    # -- anchor (pause) -----------------------------------------------

    def _pause_path(self) -> Optional[str]:
        from kubernetes_tpu import native

        path = native.pause_binary()
        if path is None:
            try:
                subprocess.run(
                    ["make", "-C", os.path.join(
                        os.path.dirname(native.__file__), "..", "..", "native"
                    ), "pause"],
                    check=True, capture_output=True,
                )
            except (OSError, subprocess.CalledProcessError):
                return None
            path = native.pause_binary()
        return path

    def _pod_dir(self, uid: str) -> str:
        return os.path.join(self.root, "pods", uid)

    # -- restart survival ---------------------------------------------

    def _record(self, uid: str, proc: _Proc) -> None:
        os.makedirs(self._pod_dir(uid), exist_ok=True)
        with open(os.path.join(self._pod_dir(uid), f"{proc.name}.json"), "w") as f:
            json.dump(
                {
                    "pid": proc.pid,
                    "hash": proc.spec_hash,
                    "name": proc.name,
                    "image": proc.image,
                    "log": proc.log_path,
                    "restartCount": proc.restart_count,
                    "anchor": proc.name == "_pause",
                },
                f,
            )

    def _adopt_existing(self) -> None:
        """Adopt recorded processes that survived a kubelet restart."""
        base = os.path.join(self.root, "pods")
        for uid in os.listdir(base):
            pod_dir = os.path.join(base, uid)
            if not os.path.isdir(pod_dir):
                continue
            for fname in os.listdir(pod_dir):
                if not fname.endswith(".json"):
                    continue
                try:
                    with open(os.path.join(pod_dir, fname)) as f:
                        rec = json.load(f)
                except (OSError, json.JSONDecodeError):
                    continue
                pid = rec.get("pid", 0)
                try:
                    os.kill(pid, 0)
                except OSError:
                    continue  # process gone; record is stale
                proc = _Proc(
                    pid=pid,
                    popen=None,
                    spec_hash=rec.get("hash", ""),
                    name=rec.get("name", ""),
                    image=rec.get("image", ""),
                    log_path=rec.get("log", ""),
                    restart_count=rec.get("restartCount", 0),
                    started_at=time.monotonic(),
                )
                if rec.get("anchor"):
                    self._anchors[uid] = proc
                else:
                    self._pods.setdefault(uid, {})[proc.name] = proc

    # -- process management -------------------------------------------

    def _start_anchor(self, uid: str) -> None:
        if uid in self._anchors and self._anchors[uid].poll() is None:
            return
        pause = self._pause_path()
        log = os.path.join(self._pod_dir(uid), "_pause.log")
        os.makedirs(self._pod_dir(uid), exist_ok=True)
        if pause is None:
            # Toolchain-less fallback: python as the anchor.
            import sys

            argv = [sys.executable, "-c", "import signal;signal.pause()"]
        else:
            argv = [pause]
        with open(log, "ab") as lf:
            popen = subprocess.Popen(
                argv,
                stdout=lf,
                stderr=lf,
                start_new_session=True,  # pod = its own process group
            )
        proc = _Proc(
            pid=popen.pid,
            popen=popen,
            spec_hash="anchor",
            name="_pause",
            image="pause",
            log_path=log,
            started_at=time.monotonic(),
        )
        self._anchors[uid] = proc
        self._record(uid, proc)

    def _container_argv(self, spec) -> List[str]:
        if spec.command:
            return list(spec.command) + list(spec.args)
        if spec.args:
            # Image entrypoint unknown in a process runtime; args alone
            # are run through the shell for convenience.
            return ["/bin/sh", "-c", " ".join(spec.args)]
        pause = self._pause_path()
        if pause is not None:
            return [pause]
        import sys

        return [sys.executable, "-c", "import signal;signal.pause()"]

    #: Accelerator/runtime plumbing that must NOT leak into pods: a
    #: workload process inheriting the node's JAX/XLA/TPU settings
    #: would reach for the accelerator the solver holds — the
    #: process-runtime analog of containers not inheriting the
    #: kubelet's device handles.
    _HOST_ONLY_ENV = (
        "JAX_PLATFORMS",
        "JAX_COMPILATION_CACHE_DIR",
        "XLA_FLAGS",
        "TPU_WORKER_HOSTNAMES",
    )

    def _env_for(self, pod: Pod, spec) -> Dict[str, str]:
        env = dict(os.environ)
        for k in self._HOST_ONLY_ENV:
            env.pop(k, None)
        # Service discovery env first (envvars.go FromServices; the
        # POD'S NAMESPACE only), then pod identity, then the
        # container's OWN env — user-declared variables win.
        env.update(
            self.service_env.get(pod.metadata.namespace or "default", {})
        )
        env["KUBERNETES_POD_NAME"] = pod.metadata.name
        env["KUBERNETES_POD_NAMESPACE"] = pod.metadata.namespace or "default"
        env["KUBERNETES_CONTAINER_NAME"] = spec.name
        if self.node_name:
            env["KUBERNETES_NODE_NAME"] = self.node_name
        if self.cluster_dns:
            env["KUBERNETES_CLUSTER_DNS"] = self.cluster_dns
            env["KUBERNETES_CLUSTER_DOMAIN"] = self.cluster_domain
        # Where this pod's mounted volumes live (host-network process
        # runtime: volumes are directories under the kubelet root,
        # <volumes-dir>/<escaped-plugin>/<volume-name>).
        uid = pod.metadata.uid or pod.metadata.name
        env["KUBERNETES_VOLUMES_DIR"] = os.path.join(
            self.root, "pods", uid, "volumes"
        )
        for e in spec.env:
            env[e.name] = e.value
        return env

    @staticmethod
    def _run_as(spec) -> Dict[str, int]:
        """SecurityContext -> Popen credential kwargs (the reference's
        securitycontext provider maps the same field onto the docker
        HostConfig User, pkg/securitycontext/provider.go). Privileged
        and capabilities have no process-level analog here; the
        SecurityContextDeny admission plugin polices them upstream."""
        ctx = getattr(spec, "security_context", None)
        if ctx is None or ctx.run_as_user is None:
            return {}
        return {
            "user": int(ctx.run_as_user),
            "group": int(ctx.run_as_user),
            "extra_groups": [],
        }

    def _start_container(
        self, pod: Pod, uid: str, spec, restart_count: int
    ) -> _Proc:
        log = os.path.join(self._pod_dir(uid), f"{spec.name}.log")
        os.makedirs(self._pod_dir(uid), exist_ok=True)
        argv = self._container_argv(spec)
        with open(log, "ab") as lf:
            try:
                popen = subprocess.Popen(
                    argv,
                    stdout=lf,
                    stderr=lf,
                    env=self._env_for(pod, spec),
                    cwd=spec.working_dir or None,
                    start_new_session=True,
                    **self._run_as(spec),
                )
            except OSError as e:
                # Start failure = immediately-exited container (the
                # reference surfaces docker run errors the same way).
                lf.write(f"start error: {e}\n".encode())
                proc = _Proc(
                    pid=0,
                    popen=None,
                    spec_hash=_spec_hash(spec),
                    name=spec.name,
                    image=spec.image,
                    log_path=log,
                    restart_count=restart_count,
                    started_at=time.monotonic(),
                    exit_code=127,
                )
                return proc
        proc = _Proc(
            pid=popen.pid,
            popen=popen,
            spec_hash=_spec_hash(spec),
            name=spec.name,
            image=spec.image,
            log_path=log,
            restart_count=restart_count,
            started_at=time.monotonic(),
        )
        self._record(uid, proc)
        return proc

    @staticmethod
    def _kill_proc(proc: _Proc, grace: float = 0.5) -> None:
        if proc.poll() is not None or proc.pid <= 0:
            return
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except OSError:
            try:
                os.kill(proc.pid, signal.SIGTERM)
            except OSError:
                return
        deadline = time.monotonic() + grace
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                break
            # Adopted processes have no popen to reap; poll via kill(0).
            if proc.popen is None:
                try:
                    os.kill(proc.pid, 0)
                except OSError:
                    break
            time.sleep(0.02)
        else:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
        if proc.popen is not None:
            try:
                proc.popen.wait(timeout=1)
            except subprocess.TimeoutExpired:
                pass

    # -- ContainerRuntime ---------------------------------------------

    def _to_rc(self, proc: _Proc) -> RuntimeContainer:
        rc = proc.poll()
        return RuntimeContainer(
            name=proc.name,
            image=proc.image,
            container_id=f"proc://{proc.pid}",
            state="running" if rc is None else "exited",
            exit_code=rc or 0,
            restart_count=proc.restart_count,
            started_at=proc.started_at,
        )

    def sync_pod(self, pod: Pod) -> List[RuntimeContainer]:
        uid = pod.metadata.uid or pod.metadata.name
        with self._lock:
            self._start_anchor(uid)
            containers = self._pods.setdefault(uid, {})
            desired = {c.name: c for c in pod.spec.containers}
            for name in list(containers):
                if name not in desired:
                    self._kill_proc(containers[name])
                    self._remove_record(uid, name)
                    del containers[name]
            for name, spec in desired.items():
                cur = containers.get(name)
                if cur is None:
                    count = self._restart_counts.pop(f"{uid}/{name}", 0)
                    containers[name] = self._start_container(
                        pod, uid, spec, count
                    )
                elif cur.spec_hash != _spec_hash(spec):
                    # Spec changed: kill + recreate (hash check,
                    # manager.go computePodContainerChanges).
                    self._kill_proc(cur)
                    containers[name] = self._start_container(
                        pod, uid, spec, cur.restart_count + 1
                    )
            return [self._to_rc(p) for p in containers.values()]

    def restart_container(self, pod_uid: str, name: str) -> None:
        with self._lock:
            cur = self._pods.get(pod_uid, {}).get(name)
            if cur is None or cur.poll() is None:
                return  # still running
            # Restart with the same argv: re-spawn from the recorded
            # spec is impossible without the Pod, so the kubelet calls
            # sync_pod right after; we just clear the exited process so
            # the next sync recreates it with restart_count + 1.
            self._kill_proc(cur)
            self._remove_record(pod_uid, name)
            del self._pods[pod_uid][name]
            self._restart_counts[f"{pod_uid}/{name}"] = cur.restart_count + 1

    def kill_pod(self, pod_uid: str) -> None:
        # Detach under the lock, kill OUTSIDE it: _kill_proc waits up
        # to the grace period per process, and holding the runtime-wide
        # lock through that would stall every other pod's sync and the
        # kubelet HTTP endpoints.
        with self._lock:
            doomed = list(self._pods.pop(pod_uid, {}).values())
            anchor = self._anchors.pop(pod_uid, None)
            if anchor is not None:
                doomed.append(anchor)
            # Drop queued restart counts: a later pod reusing this key
            # (manifest pods key by name) must start from 0.
            prefix = pod_uid + "/"
            for key in [k for k in self._restart_counts if k.startswith(prefix)]:
                del self._restart_counts[key]
        for proc in doomed:
            self._kill_proc(proc)
        shutil.rmtree(self._pod_dir(pod_uid), ignore_errors=True)

    def list_pods(self) -> Dict[str, List[RuntimeContainer]]:
        with self._lock:
            out = {
                uid: [self._to_rc(p) for p in cs.values()]
                for uid, cs in self._pods.items()
            }
            for uid, anchor in self._anchors.items():
                out.setdefault(uid, [])
            return out

    def exec_probe(
        self, pod: Pod, container: str, command: List[str], timeout: float = 1.0
    ) -> bool:
        rc, _ = self.exec_in_container(
            pod.metadata.uid or pod.metadata.name, container, command,
            pod=pod, timeout=timeout,
        )
        return rc == 0

    # -- kubelet-API surface (logs / exec / run) ----------------------

    def exec_in_container(
        self,
        pod_uid: str,
        container: str,
        command: List[str],
        pod: Optional[Pod] = None,
        timeout: float = 10.0,
    ) -> Tuple[int, str]:
        """Run a command in the container's context (env, cwd). The
        reference execs inside the container's namespaces
        (pkg/kubelet/server.go /exec); a process runtime's context is
        the container's environment."""
        with self._lock:
            proc = self._pods.get(pod_uid, {}).get(container)
        spec = None
        if pod is not None:
            spec = next(
                (c for c in pod.spec.containers if c.name == container), None
            )
        if pod is not None and spec is not None:
            env = self._env_for(pod, spec)  # full container env
        else:
            env = dict(os.environ)
            env["KUBERNETES_CONTAINER_NAME"] = container
            if pod is not None:
                env["KUBERNETES_POD_NAME"] = pod.metadata.name
                env["KUBERNETES_POD_NAMESPACE"] = (
                    pod.metadata.namespace or "default"
                )
        if proc is not None:
            env["KUBERNETES_CONTAINER_PID"] = str(proc.pid)
        try:
            done = subprocess.run(
                command,
                capture_output=True,
                env=env,
                cwd=(spec.working_dir or None) if spec is not None else None,
                timeout=timeout,
                text=True,
            )
        except (OSError, subprocess.TimeoutExpired) as e:
            return 127, str(e)
        return done.returncode, done.stdout + done.stderr

    def read_logs(
        self, pod_uid: str, container: str, tail_lines: Optional[int] = None
    ) -> str:
        with self._lock:
            proc = self._pods.get(pod_uid, {}).get(container)
        path = (
            proc.log_path
            if proc is not None
            else os.path.join(self._pod_dir(pod_uid), f"{container}.log")
        )
        try:
            with open(path, "r", errors="replace") as f:
                data = f.read()
        except OSError:
            return ""
        if tail_lines is not None and tail_lines >= 0:
            if tail_lines == 0:
                return ""  # kubectl --tail=0: suppress output
            lines = data.splitlines(keepends=True)
            data = "".join(lines[-tail_lines:])
        return data

    def fail_container(self, pod_uid: str, name: str, exit_code: int = 137) -> None:
        """Kill one container's process (liveness-probe kill path; the
        restart-policy sync brings it back)."""
        with self._lock:
            cur = self._pods.get(pod_uid, {}).get(name)
            if cur is not None:
                self._kill_proc(cur)

    # -- helpers ------------------------------------------------------

    def _remove_record(self, uid: str, name: str) -> None:
        try:
            os.unlink(os.path.join(self._pod_dir(uid), f"{name}.json"))
        except OSError:
            pass

    def anchor_pid(self, pod_uid: str) -> Optional[int]:
        with self._lock:
            anchor = self._anchors.get(pod_uid)
            return anchor.pid if anchor is not None else None
