"""The rest of the device's idle share: no operation on the device and
no `data_wait` span open, so the loop thread was in `start_of_iteration`,
`dis_step`, `gen_step`, `health_poll`, `end_of_iteration`, or between
them. With `idle_feed_starved.train` it adds up to `device_idle.train`."""

from benchmark.lib import program_spans


def read(observed):
    split = program_spans.traced_idle_split(observed)
    if not split:
        return None
    return 100.0 * (split["idle_s"] - split["covered_s"]) / split["window_s"]
