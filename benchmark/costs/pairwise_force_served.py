"""Operations one dispatch of a ``MatchServer`` group needs of the pairwise
flocking force, from shapes alone (``match_server_world`` driver's
``cost_shapes``).

31 floating-point operations a pair (``benchmark/costs/pairwise_force.py``,
whose count this imports nothing of and repeats: two differences, the
squared distance, three compares and mask products, one reciprocal square
root, seven masked accumulations) x N x N pairs a frame x the slots of one
group's dispatch x the frames a slot's tick must step: every branch of its
speculative rollout over its depth, and its live frames (a SyncTest match at
check distance d resimulates d frames and steps the new one: d + 1).

Left out, so the share this gives is a floor that reads the same useful
work whatever implements it: the masked padding steps of the tick's
fixed-length burst (the program steps ``max_prediction + 2`` frames a slot
and masks all but the live ones: 7 of 10 here, a tenth of the dispatch's
evaluations), idle lanes' replay, the hi/lo split's second and third matrix
products on the MXU path, the per-boid work after the sums (combine,
steering, clamp, wrap), and all bytes: the kernel reads 5 floats a boid and
is bound by arithmetic, not by memory.
"""

FLOPS_PER_PAIR = 31


def flops(shapes: dict) -> float:
    n = shapes["num_entities"]
    frames = (shapes["speculation_branches"] * shapes["speculation_frames"]
              + shapes["live_frames"])
    return float(FLOPS_PER_PAIR * n * n * shapes["num_slots"] * frames)
