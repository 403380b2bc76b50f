"""The Pallas step kernel's share of its roofline in the fleet cells.

Bound by bytes alone (bitwise work, no published VPU peak): the HBM
bytes each call must move (`work.step_kernel_bytes`, from the grid's
shape and each dispatch's program length - ``cycles`` of a shared
``grid.dispatch``, ``padded_to`` of a ``grid.run_per_slot``) over the
chip's HBM bandwidth, against the kernel's device time in the trace.
Percent."""
import work


def read(run):
    dev, s, f = run.device, run.spans, run.facts
    bw = run.peaks.get("hbm_bytes_per_s")
    if dev is None or s is None or not bw:
        return None
    k = dev["kernels"].get("comefa_step", {})
    if not k.get("calls") or k["seconds"] <= 0:
        return None
    if f["per_slot"]:
        lengths = [int(e.attrs["padded_to"])
                   for e in s.named("grid.run_per_slot")]
    else:
        lengths = [int(e.attrs["cycles"]) for e in s.named("grid.dispatch")]
    if not lengths:
        return None
    per_call = sum(work.step_kernel_bytes(
        f["slots"], f["n_blocks"], t, f["n_fields"], per_slot=f["per_slot"])
        for t in lengths) / len(lengths)
    return 100.0 * (k["calls"] * per_call / bw) / k["seconds"]
