"""One scalar of the run as it is (``setup_s``)."""


def read(spec, results):
    value = results.scalars.get(spec["key"])
    return None if value is None else float(value)
