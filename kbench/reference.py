"""The plain reference that decides ``correct``. It imports nothing of
the program: numpy, the standard library and ``kbench.gen``.

Semantics are those of the reference scheduler's default provider
(plugin/pkg/scheduler: predicates.go, priorities.go, spreading.go at
v0.19), written out from its description:

- predicates: PodFitsResources (sum of container limits against
  capacity, and the pod count), MatchNodeSelector, PodFitsPorts;
- priorities, weight 1 each: LeastRequested
  ``((cap - req) * 10 // cap`` per resource, averaged), Balanced
  ResourceAllocation (``10 - |cpu_frac - mem_frac| * 10``, 0 once a
  fraction reaches 1) and ServiceSpreading
  (``10 * (max - count) // max`` over the pod's service's pods per
  node, 10 when it has no service); the chosen node is one of those with the
  highest total. The reference breaks ties at random, so any node of
  the highest total is correct.

Three checks, each a number with a limit (exact 0 for the first two;
the traffic's ``mismatch_limit`` for the third, set in PERF.md from
the program's and the control's readings):

- ``unbound``: pods created (the API accepted them) that no watch
  ever showed bound by the drain deadline: answers that never came;
- ``violations``: bindings in the store, as listed after the run, that
  break a predicate on their node, plus bindings where the store and
  the watch disagree;
- ``mismatch``: the share of a seeded sample of the run's decisions
  (warm-up, window and drain; a sample as large as the traffic's
  ``check_sample``, which for an open-loop mix can take them all) that were not a
  best feasible node. Each decision is judged against
  the cluster the program had built by then: the prefill, every bind
  before it and every delete before it, in the store's commit order as
  the watch delivered it. A delete that the watch showed less than
  the traffic's ``visibility_slack_s`` before the pod was created may
  not yet have reached the scheduler's own watch when it solved; for
  the nodes such deletes touch the check allows any of the states they
  could have been in.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from kbench import gen

#: Float rounding allowed at a truncation boundary of Balanced
#: ResourceAllocation (the program computes the fractions in float32).
BRA_ROUNDING = 2e-5


class Pods:
    """Pod specs by name, regenerated from the seed."""

    def __init__(self, cfg: dict, seed: int, prefill: List[Tuple[gen.PodSpec, str]]):
        self.cfg, self.seed = cfg, seed
        self.by_name: Dict[str, gen.PodSpec] = {p.name: p for p, _ in prefill}
        self._streams: Dict[str, int] = {}

    def get(self, name: str) -> Optional[gen.PodSpec]:
        p = self.by_name.get(name)
        if p is not None:
            return p
        m = re.fullmatch(r"c(\d+)", name)
        if m:
            n = int(m.group(1)) + 1
            if self._streams.get("churn", 0) < n:
                n = max(n, 2 * self._streams.get("churn", 0))
                for q in gen.pods(self.cfg, self.seed, "churn", "c", n):
                    self.by_name[q.name] = q
                self._streams["churn"] = n
            return self.by_name.get(name)
        m = re.fullmatch(r"b(\d+)-(\d+)", name)
        if m and f"burst{m.group(1)}" not in self._streams:
            k = m.group(1)
            B = self.cfg["pending_pods"]
            for q in gen.pods(self.cfg, self.seed, f"burst{k}", f"b{k}-", B):
                self.by_name[q.name] = q
            self._streams[f"burst{k}"] = B
            return self.by_name.get(name)
        return None


class Cluster:
    """Node occupancy under the predicates' and priorities' arithmetic."""

    def __init__(self, nodes: List[gen.NodeSpec], n_services: int):
        self.names = [n.name for n in nodes]
        self.index = {n: j for j, n in enumerate(self.names)}
        self.cap_cpu = np.array([n.cpu_m for n in nodes], np.int64)
        self.cap_mem = np.array([n.mem_mib for n in nodes], np.int64)
        self.cap_pods = np.array([n.pods for n in nodes], np.int64)
        self.zone = np.array([n.zone for n in nodes])
        N = len(nodes)
        self.cpu = np.zeros(N, np.int64)
        self.mem = np.zeros(N, np.int64)
        self.count = np.zeros(N, np.int64)
        self.svc = np.zeros((n_services, N), np.int64)
        self.ports: List[Dict[int, int]] = [{} for _ in range(N)]
        self.where: Dict[str, Tuple[int, gen.PodSpec]] = {}

    def add(self, p: gen.PodSpec, j: int, sign: int = 1) -> None:
        self.cpu[j] += sign * p.cpu_m
        self.mem[j] += sign * p.mem_mib
        self.count[j] += sign
        if p.app >= 0:
            self.svc[p.app, j] += sign
        if p.host_port:
            d = self.ports[j]
            d[p.host_port] = d.get(p.host_port, 0) + sign

    def bind(self, p: gen.PodSpec, j: int) -> None:
        self.where[p.name] = (j, p)
        self.add(p, j)

    def delete(self, name: str) -> Optional[Tuple[int, gen.PodSpec]]:
        hit = self.where.pop(name, None)
        if hit is not None:
            self.add(hit[1], hit[0], -1)
        return hit

    # -- one pod against every node ----------------------------------

    def feasible(self, p: gen.PodSpec, cpu, mem, count, ports_ok) -> np.ndarray:
        ok = (count + 1 <= self.cap_pods)
        ok &= cpu + p.cpu_m <= self.cap_cpu
        ok &= mem + p.mem_mib <= self.cap_mem
        if p.zone:
            ok &= self.zone == p.zone
        return ok & ports_ok



def lr_bra(p: gen.PodSpec, cpu, mem, cc, mc) -> Tuple[np.ndarray, np.ndarray]:
    """(low, high) of LeastRequested + BalancedResourceAllocation: the
    two differ only where float rounding can cross a truncation
    boundary of the balance score."""
    cr, mr = cpu + p.cpu_m, mem + p.mem_mib

    def least(req, cap):
        return np.where(req > cap, 0, ((cap - req) * 10) // np.maximum(cap, 1))

    lr = (least(cr, cc) + least(mr, mc)) // 2
    cf, mf = cr / cc, mr / mc
    x = 10 - np.abs(cf - mf) * 10
    lo = np.floor(x - BRA_ROUNDING).astype(np.int64)
    hi = np.floor(x + BRA_ROUNDING).astype(np.int64)
    full = (cf >= 1) | (mf >= 1)
    edge = (cr == cc) | (mr == mc)  # a fraction of exactly 1
    lo = np.where(full, 0, lo)
    hi = np.where(full & ~edge, 0, hi)
    return lr + np.maximum(lo, 0), lr + np.maximum(hi, 0)


def _spread(maxc, counts: np.ndarray) -> np.ndarray:
    maxc = np.broadcast_to(np.asarray(maxc, np.int64), counts.shape)
    return np.where(
        maxc <= 0, 10, (10 * (maxc - counts)) // np.maximum(maxc, 1)
    )


def judge(cl: Cluster, p: gen.PodSpec, chosen: int,
          restore: Dict[int, List[gen.PodSpec]]) -> Tuple[bool, int]:
    """Was ``chosen`` a best feasible node for ``p``? ``restore`` maps
    a node to the pods deleted from it that the solve may still have
    seen, in delete order (the solve saw a prefix of all deletes, so a
    node's possible states put back a suffix of its list). Returns
    (ok, score gap)."""
    port_ok = np.ones(len(cl.names), bool)
    if p.host_port:
        port_ok = np.array([not d.get(p.host_port) for d in cl.ports])
    feas = cl.feasible(p, cl.cpu, cl.mem, cl.count, port_ok)
    lo, hi = lr_bra(p, cl.cpu, cl.mem, cl.cap_cpu, cl.cap_mem)
    counts = cl.svc[p.app] if p.app >= 0 else np.zeros(len(cl.names), np.int64)
    c_lo = counts.copy()
    c_hi = counts.copy()
    feas_all, feas_any = feas.copy(), feas.copy()
    if restore:
        # One row per possible state of a node: its last m deletes put
        # back, for m = 1 .. all of them.
        J, add_cpu, add_mem, add_cnt, port_hit, same = [], [], [], [], [], []
        for j, gone in restore.items():
            cpu = mem = hit = app = 0
            for m, q in enumerate(reversed(gone), 1):
                cpu += q.cpu_m
                mem += q.mem_mib
                hit |= bool(p.host_port) and q.host_port == p.host_port
                app += p.app >= 0 and q.app == p.app
                J.append(j), add_cpu.append(cpu), add_mem.append(mem)
                add_cnt.append(m), port_hit.append(hit), same.append(app)
        J = np.array(J)
        cpu = cl.cpu[J] + np.array(add_cpu)
        mem = cl.mem[J] + np.array(add_mem)
        f = (
            (cl.count[J] + np.array(add_cnt) + 1 <= cl.cap_pods[J])
            & (cpu + p.cpu_m <= cl.cap_cpu[J])
            & (mem + p.mem_mib <= cl.cap_mem[J])
            & port_ok[J] & ~np.array(port_hit, bool)
        )
        if p.zone:
            f &= cl.zone[J] == p.zone
        l, h = lr_bra(p, cpu, mem, cl.cap_cpu[J], cl.cap_mem[J])
        np.logical_and.at(feas_all, J, f)
        np.logical_or.at(feas_any, J, f)
        np.minimum.at(lo, J, l)
        np.maximum.at(hi, J, h)
        np.maximum.at(c_hi, J, counts[J] + np.array(same))
    m_lo, m_hi = int(c_lo.max()), int(c_hi.max())
    # Spreading rises with the maximum and falls with the node's count
    # (and the maximum is never below the node's count).
    s_lo = _spread(np.maximum(m_lo, c_hi), c_hi)
    s_hi = _spread(m_hi, c_lo)
    lo_t, hi_t = lo + s_lo, hi + s_hi
    rivals = np.where(feas_all, lo_t, -1)
    if chosen < 0 or not feas_any[chosen]:
        return False, int(rivals.max()) + 1
    best_rival = int(rivals.max())
    return hi_t[chosen] >= best_rival, best_rival - int(hi_t[chosen])


def check(cfg: dict, traffic: dict, seed: int, run: dict) -> Dict[str, dict]:
    """The three checks over one run's loadgen record. The traffic
    gives the sample's size (``check_sample``), the seconds a delete may
    take to reach the scheduler's watch after it reached the
    benchmark's (``visibility_slack_s``) and ``mismatch_limit``."""
    sample, slack = traffic["check_sample"], traffic["visibility_slack_s"]
    node_list = gen.nodes(cfg, seed)
    prefill = gen.prefill(cfg, seed, node_list)
    pods = Pods(cfg, seed, prefill)
    cl = Cluster(node_list, gen.services(cfg))
    for p, node in prefill:
        cl.bind(p, cl.index[node])

    due = run["due"]
    bind_seq = {e[2]: e[0] for e in run["watch"] if e[1] == "B"}
    decided = sorted(bind_seq[n] for n in run["created"] if n in bind_seq)
    r = gen.rng(seed, "sample")
    picked = set(r.sample(decided, min(sample, len(decided))))

    deletes: List[Tuple[float, int, gen.PodSpec]] = []  # (t_recv, node, pod)
    judged = wrong = 0
    worst_gap = 0
    watch_node: Dict[str, str] = {}
    for seq, kind, name, node, t in run["watch"]:
        if kind == "D":
            hit = cl.delete(name)
            if hit is not None:
                deletes.append((t, hit[0], hit[1]))
            continue
        p = pods.get(name)
        j = cl.index.get(node, -1)
        watch_node[name] = node
        if p is None or name in cl.where:
            continue
        if seq in picked:
            cut = due[name] - slack
            restore: Dict[int, List[gen.PodSpec]] = {}
            for t_d, jd, q in reversed(deletes):
                if t_d < cut:
                    break
                restore.setdefault(jd, []).insert(0, q)
            ok, gap = judge(cl, p, j, restore)
            judged += 1
            if not ok:
                wrong += 1
                worst_gap = max(worst_gap, gap)
        if j >= 0:
            cl.bind(p, j)

    unbound = sum(1 for n in run["created"] if n not in bind_seq)
    violations = _violations(node_list, pods, run["bindings"], watch_node)
    return {
        "unbound": {"value": unbound, "limit": 0},
        "violations": {"value": violations, "limit": 0},
        "mismatch": {
            "value": wrong / judged if judged else 1.0,
            "limit": traffic["mismatch_limit"],
            "judged": judged, "worst_gap": worst_gap,
        },
    }


def _violations(node_list, pods: Pods, bindings, watch_node) -> int:
    """Bindings the store lists that break a predicate on their node:
    capacity (cpu, memory, pods), selector, host ports; plus bindings
    on unknown nodes and store/watch disagreements."""
    by_node: Dict[str, List[gen.PodSpec]] = {}
    bad = 0
    for name, node in bindings:
        if not node:
            continue
        if name in watch_node and watch_node[name] != node:
            bad += 1
        p = pods.get(name)
        if p is None:
            bad += 1
            continue
        by_node.setdefault(node, []).append(p)
    spec = {n.name: n for n in node_list}
    for node, bound in by_node.items():
        n = spec.get(node)
        if n is None:
            bad += len(bound)
            continue
        bad += sum(p.cpu_m for p in bound) > n.cpu_m
        bad += sum(p.mem_mib for p in bound) > n.mem_mib
        bad += len(bound) > n.pods
        bad += sum(1 for p in bound if p.zone and p.zone != n.zone)
        ports = [p.host_port for p in bound if p.host_port]
        bad += len(ports) - len(set(ports))
    return bad
