"""Share of its roofline that the SSD chunk kernel reaches in the train step.

The kernel's least time per call, from its shapes
(`bench.flops_hybrid.ssd_roofline_s`: the larger of its FLOPs over the bf16
peak and its bytes over HBM's bandwidth, for one layer's batch and
sequence), times its calls per step, over its device seconds per step.
Both come from the driver's profile of its own steps before the window
(``ssd_kernel_calls``, ``ssd_kernel_s``: every ``%ssd`` custom call on the
device, none left out).  None where no kernel ran, on a chip with no
peaks, or where the driver took no profile."""

from bench.flops_hybrid import ssd_roofline_s


def read(rec):
    peaks, c = rec["peaks"], rec["counters"]
    if peaks is None or not c.get("ssd_kernel_calls") or not c.get(
            "ssd_kernel_s"):
        return None
    least = ssd_roofline_s(rec["config"], c["batch"], c["seq"], peaks)
    return 100.0 * c["ssd_kernel_calls"] * least / c["ssd_kernel_s"]
