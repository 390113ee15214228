"""Least bytes one dispatch of the ``[S]``-vmapped tick must move.

From shapes alone (``match_server`` driver's ``cost_shapes``): every slot's
live state is read and written once; each frame the tick saves writes one
ring row per slot (a SyncTest tick at check distance d saves 1 + (d + 1)
frames over its two dispatches, so at least one row per dispatch); and the
speculative rollout's ``[S, B]`` states and ``[S, B, F]`` ring rows are
written once. Re-reading, padding and the burst's intermediate states are
not counted: this is a floor, so the share it gives is an upper bound on
nothing and a lower bound on how far the program is from its roofline.
"""


def least_bytes(shapes: dict) -> float:
    ring_row = shapes["slot_rings_bytes"] / shapes["ring_depth"]
    return float(
        2 * shapes["slot_states_bytes"]
        + ring_row
        + shapes["spec_states_bytes"]
        + shapes["spec_rings_bytes"]
    )
