"""A reduction of one of the benchmark's own sample series (host clock)."""

from benchmark.readers.common import reduce_samples


def read(spec, results):
    return reduce_samples(results.series.get(spec["series"], []),
                          spec["reduce"])
