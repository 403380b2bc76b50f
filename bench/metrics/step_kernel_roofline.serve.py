"""The Pallas step kernel's share of its roofline in the serving cell.

The kernel is bitwise integer work with no published VPU peak, so its
roofline is bound by bytes alone: the HBM bytes each call must move
(`work.step_kernel_bytes`: packed state in and out plus the program
matrix, from the shapes of every ``grid.dispatch`` in the window, with
the block count from its ``kernel.gemv_batched`` parent) over the
chip's HBM bandwidth, against the kernel's device time in the trace.
Percent."""
import work

LANES = 160


def read(run):
    dev, s = run.device, run.spans
    bw = run.peaks.get("hbm_bytes_per_s")
    if dev is None or s is None or not bw:
        return None
    k = dev["kernels"].get("comefa_step", {})
    if not k.get("calls") or k["seconds"] <= 0:
        return None
    total = calls = 0
    for parent in s.named("kernel.gemv_batched"):
        nb = -(-int(parent.attrs["n"]) // LANES)
        for d in s.children(parent, lambda e: e.name == "grid.dispatch"):
            total += work.step_kernel_bytes(
                int(d.attrs["slots"]), nb, int(d.attrs["cycles"]),
                run.facts["n_fields"], per_slot=False)
            calls += 1
    if not calls:
        return None
    return 100.0 * (total / bw) / k["seconds"]
