"""Model FLOP/s utilisation: the operations the forward and backward
passes of one iteration need at the cell's shapes (the reference's own
count, recomputation not counted), times the window's iterations, over
the window's seconds, the chips and the device kind's bf16 peak."""


def read(observed):
    flops = (observed.get("step_flops") or {}).get("iteration")
    peaks = observed.get("peaks")
    if not flops or not peaks or not observed.get("window_s"):
        return None
    achieved = flops * observed["iterations"] / observed["window_s"]
    return 100.0 * achieved / (observed["chips"] * peaks["bf16_flops_per_s"])
