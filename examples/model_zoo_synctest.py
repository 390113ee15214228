#!/usr/bin/env python
"""SyncTest determinism harness for the extension models (boids,
neural_bots, projectiles, particles) — the box_game CLIs cover reference parity; this
drives the entity-scaling, MXU, and dynamic-lifecycle model families
through the same forced-rollback machinery.

    python examples/model_zoo_synctest.py --model boids --entities 512 \
        --check-distance 5 --frames 120 --kernel mxu
    python examples/model_zoo_synctest.py --model neural_bots --platform tpu
    python examples/model_zoo_synctest.py --model projectiles
    python examples/model_zoo_synctest.py --model particles --entities 9216
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from box_game_common import (  # noqa: E402
    Instruments,
    add_common_args,
    force_platform,
)

import numpy as np  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model",
                        choices=["boids", "neural_bots", "projectiles",
                                 "particles"],
                        default="boids")
    parser.add_argument("--entities", type=int, default=256)
    parser.add_argument("--num-players", type=int, default=2)
    parser.add_argument("--check-distance", type=int, default=4)
    parser.add_argument("--kernel", choices=["xla", "mxu"], default="xla",
                        help="boids force kernel (mxu = the Pallas kernel, "
                             "matmul reductions: what the chip runs)")
    add_common_args(parser)
    args = parser.parse_args()
    force_platform(args.platform)

    from bevy_ggrs_tpu.models import boids, neural_bots, particles, projectiles
    from bevy_ggrs_tpu.runner import RollbackRunner
    from bevy_ggrs_tpu.session import MismatchedChecksum, SyncTestSession
    from bevy_ggrs_tpu.state import combine64, checksum

    if args.model == "boids":
        model = boids
        schedule = boids.make_schedule(kernel=args.kernel)
        world = boids.make_world(args.entities, args.num_players)
    elif args.model == "projectiles":
        model = projectiles
        schedule = projectiles.make_schedule()
        world = projectiles.make_world(
            args.num_players, capacity=args.entities
        )
    elif args.model == "particles":
        # Upstream's stress test: 100 births a frame want 8,900 rows; a
        # smaller world takes as many births as fit its --entities.
        model = particles
        rate = max(1, min(particles.RATE, args.entities // 89))
        schedule = particles.make_schedule(rate)
        world = particles.make_world(args.num_players, args.entities)
    else:
        model = neural_bots
        schedule = neural_bots.make_schedule()
        world = neural_bots.make_world(args.entities, args.num_players)

    max_prediction = max(8, args.check_distance)
    session = SyncTestSession(
        args.num_players, model.INPUT_SPEC,
        check_distance=args.check_distance, max_prediction=max_prediction,
    )
    runner = RollbackRunner(
        schedule, world.commit(), max_prediction=max_prediction,
        num_players=args.num_players, input_spec=model.INPUT_SPEC,
    )
    inst = Instruments(args)
    if inst.metrics is not None:
        runner.metrics = inst.metrics

    rng = np.random.RandomState(0)
    # projectiles adds a FIRE bit (1<<4) — include it so the harness
    # exercises spawn/despawn under the forced rollbacks.
    hi = 32 if args.model == "projectiles" else 16
    try:
        with inst:
            for i in range(args.frames):
                for h in range(args.num_players):
                    session.add_local_input(h, np.uint8(rng.randint(0, hi)))
                runner.handle_requests(session.advance_frame(), session)
    except MismatchedChecksum as exc:
        print(f"DESYNC: {exc}", file=sys.stderr)
        return 1

    fc = int(np.asarray(runner.state.resources["frame_count"]))
    print(f"[{args.model} synctest ok] frames={runner.frame} "
          f"frame_count={fc} entities={args.entities} "
          f"rollbacks={runner.rollbacks_total} "
          f"resimulated={runner.rollback_frames_total} "
          f"final_checksum={hex(combine64(checksum(runner.state)))}")
    inst.finish()
    return 0


if __name__ == "__main__":
    sys.exit(main())
