"""A program's share of its memory roofline, in %: the least bytes one
execution must move (a function under ``benchmark/costs``, from shapes)
over the device's peak bandwidth (``benchmark/peaks.json``), over the
program's measured device time (another per-layer metric, read before)."""

import importlib


def read(spec, results):
    program_ms = results.values.get(spec["program_metric"])
    if not program_ms or not results.cost_shapes or results.peaks is None:
        return None
    module, func = spec["bytes_function"].split(":")
    least = getattr(importlib.import_module(module), func)(results.cost_shapes)
    peak = float(results.peaks[spec["peak"]])
    return 100.0 * (least / peak) / (program_ms * 1e-3)
