"""Share of the window the loop spent blocked in `next(feed)` (harness
clock around the program's own timed iterator)."""


def read(observed):
    if observed.get("feed_wait_s") is None or not observed.get("window_s"):
        return None
    return 100.0 * observed["feed_wait_s"] / observed["window_s"]
