"""Test configuration: force an 8-device virtual CPU platform so
multi-chip sharding paths (Mesh/pjit/shard_map) are exercised without
TPU hardware, with pallas kernels in interpret mode.

- JAX_PLATFORMS is set in the environment (inherited by the daemons
  tests start as subprocesses) and again through jax.config, which
  holds even if something imported jax before this file ran.
- XLA_FLAGS must be set before the CPU backend initializes, which
  happens at first use, so setting it here works.
- The persistent compilation cache is off for the whole run, in this
  process and in its subprocesses (whose entry points would otherwise
  place it in the checkout, utils/compile_cache.py): tests write
  nothing there.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import threading  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402  (after env setup on purpose)
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

#: Tier-1 runs these concurrency-heavy modules with the ktsan runtime
#: sanitizer ON (utils/sanitizer.py): their tests construct fresh
#: stores / watch caches / daemons per test, so every hot lock is
#: instrumented, and the teardown guard below fails the test on any
#: lock-order inversion, blocking-call-under-lock, lock held by a dead
#: thread, or leaked non-daemon thread. The empty-findings gate IS the
#: ktsan baseline — and it must stay empty.
KTSAN_MODULES = {
    "test_store",
    "test_watchcache",
    "test_gang",
    "test_preemption",
    "test_ktsan",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: at-scale tests (minutes); run with --runslow"
    )
    config.addinivalue_line(
        "markers",
        "gang: gang-scheduling (PodGroup) tests; tier-1 includes them — "
        "select just these with -m gang",
    )
    config.addinivalue_line(
        "markers",
        "preempt: priority & preemption (PriorityClass/eviction) tests; "
        "tier-1 includes them — select just these with -m preempt",
    )
    config.addinivalue_line(
        "markers",
        "explain: scheduling explainability (flight recorder / explain "
        "readback / ktctl explain) tests; tier-1 includes them — select "
        "just these with -m explain",
    )
    config.addinivalue_line(
        "markers",
        "slo: SLI/SLO telemetry-plane tests (lifecycle collector, watch "
        "fan-out lag/drops, SLO engine, ktctl slo); tier-1 includes "
        "them — select just these with -m slo",
    )
    config.addinivalue_line(
        "markers",
        "profiler: device-time profiling-plane tests (compile/cost "
        "ledger, duty-cycle/overlap series, ktctl profile, device "
        "traces); tier-1 includes them — select just these with "
        "-m profiler",
    )
    config.addinivalue_line(
        "markers",
        "ktshape: kernel shape/dtype/sharding contract-checker tests "
        "(KT007 fixtures, abstract-eval/jaxpr-walk fixtures, live-tree "
        "contract gate); tier-1 includes them — select just these with "
        "-m ktshape",
    )
    config.addinivalue_line(
        "markers",
        "capacity: capacity & fragmentation observability-plane tests "
        "(capacity kernel twins, monitor, /debug/capacity, ktctl top "
        "capacity, capacity SLO objectives); tier-1 includes them — "
        "select just these with -m capacity",
    )
    config.addinivalue_line(
        "markers",
        "rebalance: continuous-rebalancing plane tests (plan_moves "
        "kernel twins, descheduler move protocol, /debug/rebalance, "
        "ktctl rebalance, rebalance SLO objectives); tier-1 includes "
        "them — select just these with -m rebalance",
    )
    config.addinivalue_line(
        "markers",
        "autoscale: elastic node-pool autoscaler tests (grow on "
        "starvation, cordon-drain-shrink on idle, pool metrics); "
        "tier-1 includes them — select just these with -m autoscale",
    )
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (utils/faults.py "
        "registry, injection sites, client resilience, crash-recovery "
        "properties); tier-1 includes them — select just these with "
        "-m chaos",
    )
    config.addinivalue_line(
        "markers",
        "soak: hollow-node soak-harness tests (tools/soak.py cluster, "
        "fault epochs, invariant checker); tier-1 includes the small "
        "ones — select with -m soak",
    )
    config.addinivalue_line(
        "markers",
        "ktmesh: static SPMD partitioning-analyzer tests (KT009 "
        "fixtures, sharding contracts / collective inventories / "
        "communication budgets, live-tree mesh gate); tier-1 includes "
        "them — select just these with -m ktmesh",
    )
    config.addinivalue_line(
        "markers",
        "health: cluster health-plane tests (time-series retention, "
        "burn-rate alert engine, /debug/{alerts,timeseries,health}, "
        "ktctl alerts / top health); tier-1 includes them — select "
        "just these with -m health",
    )
    config.addinivalue_line(
        "markers",
        "sanitize: run this test with the ktsan lock sanitizer enabled "
        "(KT_SANITIZE=locks equivalent) and fail it on any sanitizer "
        "finding or leaked non-daemon thread; the concurrency-heavy "
        "modules in conftest.KTSAN_MODULES get this implicitly",
    )


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run slow at-scale tests",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: needs --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def host_mesh():
    """Factory for 1-D host-platform meshes over the forced 8-device
    CPU platform, routed through the ONE sanctioned constructor
    (ops.matrices.host_mesh) so tests exercise the same seam sessions
    and the KT_MESH_DEVICES hatch use. Call with n (and optionally the
    axis name); asserts the mesh actually formed — under this conftest
    8 devices are guaranteed, so None means the env setup broke."""
    from kubernetes_tpu.ops import matrices

    def make(n: int, axis: str = "nodes"):
        mesh = matrices.host_mesh(n, axis=axis)
        assert mesh is not None, (
            f"host_mesh({n}) returned None with {len(jax.devices())} "
            "visible devices — the forced 8-device CPU platform did "
            "not take (XLA_FLAGS set after backend init?)"
        )
        return mesh

    return make


@pytest.fixture()
def mesh_subprocess_env():
    """os.environ copy for subprocesses that must see the same forced
    8-device CPU platform as the in-process tests (CLI gates, ktmesh
    subprocess runs). A bare copy is NOT enough on machines where the
    parent inherited different XLA_FLAGS pre-conftest."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    return env


@pytest.fixture(autouse=True)
def _ktsan_guard(request):
    """Per-test ktsan harness: sanitizer-on for KTSAN_MODULES /
    @pytest.mark.sanitize / KT_SANITIZE=locks runs, with a thread
    snapshot so a test that leaks a non-daemon thread (or a lock held
    by a dead thread) fails HERE, not as a hang three modules later.

    Enablement is creation-time: locks built BEFORE enable() (e.g. in
    module-scoped fixtures) stay plain — tests in the sanitized
    modules construct their stores/daemons per test, which is exactly
    what makes per-test enablement effective. The KT_SANITIZE env
    path instruments import-time singletons too."""
    from kubernetes_tpu.utils import sanitizer

    module = request.node.module.__name__.rpartition(".")[2]
    env_on = sanitizer.enabled()
    want = (
        env_on
        or module in KTSAN_MODULES
        or request.node.get_closest_marker("sanitize") is not None
    )
    if not want:
        yield
        return
    sanitizer.enable()
    sanitizer.reset()
    before = {t.ident for t in threading.enumerate()}
    yield

    def fresh_nondaemon():
        return [
            t for t in threading.enumerate()
            if t.ident not in before and t.is_alive() and not t.daemon
        ]

    # Let teardown-stopped workers actually exit before judging.
    deadline = time.monotonic() + 2.0
    while fresh_nondaemon() and time.monotonic() < deadline:
        time.sleep(0.02)
    leaked_threads = [t.name for t in fresh_nondaemon()]
    found = sanitizer.findings()
    dead_held = sanitizer.leaked_locks()
    sanitizer.reset()
    if dead_held:
        # Reported below — forget the dead holders so ONE real leak
        # fails one test instead of cascading into every later one.
        sanitizer.purge_dead_threads()
    if not env_on:
        sanitizer.disable()
    problems = []
    if found:
        problems.append(f"ktsan findings: {found}")
    if dead_held:
        problems.append(f"locks held by dead threads: {dead_held}")
    if leaked_threads:
        problems.append(f"leaked non-daemon threads: {leaked_threads}")
    if problems:
        pytest.fail("ktsan: " + "; ".join(problems))
