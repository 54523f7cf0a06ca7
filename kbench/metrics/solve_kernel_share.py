"""Share of the sequential-parity solves the pallas kernel ran, in %:
``scheduler_device_solves_total`` with ``kernel="pallas"`` over the
family's total (``pallas`` and ``xla_scan``), read from the program's
registry in the process that ran the cell. The harness snapshots no
counter of this family at the window's edges, so the share is the
run's (pre-warm, warm-up, window and drain): the kernel is chosen once
for a session's shapes, so it is the window's share too. A program
without the counter reads nothing."""


def read(ctx):
    from kubernetes_tpu.utils import metrics

    fam = metrics.DEFAULT.get("scheduler_device_solves_total")
    if fam is None:
        return None
    by_kernel: dict = {}
    for (kernel, reason), n in fam.snapshot().items():
        by_kernel[kernel] = by_kernel.get(kernel, 0.0) + n
        ctx["notes"].append(f"device solves: {kernel} ({reason}) {n:.0f}")
    total = sum(by_kernel.values())
    if not total:
        return None
    return 100.0 * by_kernel.get("pallas", 0.0) / total
