"""Work completed per second: a count of the run over all the time of the
window (the wait for the device to finish included)."""


def read(spec, results):
    count = results.scalars.get(spec["count"])
    if count is None or results.window_s <= 0:
        return None
    return float(count) / results.window_s
