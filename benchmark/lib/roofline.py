"""A scope's share of its roofline: the least time the chip could take
for the scope's work (the larger of its operations over the bf16 peak and
its bytes over the memory's peak, both from the reference's `*_work`
functions at the cell's shapes) over the device time measured under the
scope. The same functions whatever implements the scope."""

from benchmark.lib import scope_times


def share(observed, work, scope):
    counted = (observed.get("work") or {}).get(work)
    peaks = observed.get("peaks")
    measured_ms = scope_times.under(observed, scope)
    if not counted or not peaks or not measured_ms:
        return None
    operations, nbytes = counted
    least_s = max(operations / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)
