"""Operations one tick of a speculating boids client needs of the pairwise
flocking force, from shapes alone (``p2p_pair_world`` driver's
``cost_shapes``).

31 floating-point operations a pair (the count ``BENCH_DETAIL.json``'s
``flop_model`` states: two differences, the squared distance, three
compares and mask products, one reciprocal square root, seven masked
accumulations) x N x N pairs a frame x the frames a tick must step: every
branch of the speculative rollout over its depth, and the one live frame.

Left out, so the share this gives is a floor: the frames a rollback
resimulates, the padding steps of the tick's fixed-length burst (the program
steps ``max_prediction + 2`` frames a tick and masks all but the live ones),
the hi/lo split's second and third matrix products on the MXU path, the
per-boid work after the sums (combine, steering, clamp, wrap), and all bytes:
the kernel reads 5 floats a boid and is bound by arithmetic, not by memory.
"""

FLOPS_PER_PAIR = 31


def flops(shapes: dict) -> float:
    n = shapes["num_entities"]
    frames = (shapes["speculation_branches"] * shapes["speculation_frames"]
              + shapes["live_frames"])
    return float(FLOPS_PER_PAIR * n * n * frames)
