"""A reduction of one series of the program's ``utils.metrics.Metrics``
sink, over the samples observed inside the window (traced run only)."""

from benchmark.readers.common import reduce_samples


def read(spec, results):
    return reduce_samples(results.program_series.get(spec["key"], []),
                          spec["reduce"])
