"""Share (%) of the device-busy time that lies outside the cell's fused
kernels: one minus their device time in the trace over the union of
device-busy intervals in the traced window.  What the device does besides
the macro kernel (weight packing, staging copies, folds, the readout)."""


def read(rec):
    t = rec.get("trace")
    if t is None or t.busy_s <= 0:
        return None
    kernel_s = sum(s for s, _ in t.kernels.values())
    if kernel_s <= 0:
        return None
    return 100.0 * (1.0 - kernel_s / t.busy_s)
