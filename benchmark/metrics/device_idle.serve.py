"""Share of the traced window in which no operation ran on the device:
1 - busy/window, busy being the union of device-operation intervals."""


def read(observed):
    trace = observed.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
