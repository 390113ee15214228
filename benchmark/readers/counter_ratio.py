"""A ratio of the program's exact counters over the window, times
``scale``; nothing where the denominator counted nothing."""


def read(spec, results):
    num = sum(results.counters.get(k, 0) for k in spec["numerator"])
    den = sum(results.counters.get(k, 0) for k in spec["denominator"])
    if den <= 0:
        return None
    return float(spec.get("scale", 1.0)) * num / den
