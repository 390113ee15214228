"""The benchmark's plain NumPy reference: equal to the program's own NumPy
twin and to the JAX step, and failing the lower-precision control."""

import json
import os

import numpy as np
import pytest

from benchmark.reference import box_game_np as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MASKS = [0, 1, 2, 4, 5, 6, 8, 9, 10]


def _table(seed, matches, players, frames):
    rng = np.random.RandomState(seed)
    return rng.choice(np.arange(16, dtype=np.uint8),
                      size=(matches, players, frames))


def _configs():
    """Every configuration of the manifest, as {name: its file's content}:
    one a later PR adds is held to its own limits by the test below."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        entries = json.load(f)["configs"]
    out = {}
    for c in entries:
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            out[c["name"]] = json.load(f)
    return out


def test_reference_imports_nothing_of_the_program():
    with open(ref.__file__, encoding="utf-8") as f:
        src = f.read()
    assert "import jax" not in src and "from jax" not in src
    assert "import bevy_ggrs_tpu" not in src and "from bevy" not in src


@pytest.mark.parametrize("players", [2, 4])
def test_reference_is_bitwise_the_programs_numpy_twin(players):
    from bevy_ggrs_tpu.models import box_game
    from bevy_ggrs_tpu.state import to_host

    frames = 200
    bits = _table(3, 1, players, frames)
    host = to_host(box_game.make_world(players).commit())
    for f in range(frames):
        host = box_game.step_np(host, bits[0, :, f])
    t, v, n = ref.replay(bits, np.asarray([frames]))
    assert np.array_equal(host["components"]["translation"][:players], t[0])
    assert np.array_equal(host["components"]["velocity"][:players], v[0])
    assert int(host["resources"]["frame_count"]) == int(n[0]) == frames


def test_reference_against_the_jax_step_and_each_matchs_own_length():
    import jax

    from benchmark.titles import box_game as title

    players, frames = 2, 120
    bits = _table(5, 3, players, frames)
    lengths = np.asarray([frames, 37, 0])
    want_t, want_v, want_n = ref.replay(bits, lengths)
    step = jax.jit(title.make_schedule())
    from bevy_ggrs_tpu.schedule import make_inputs

    for m, n in enumerate(lengths):
        state = title.make_world(players)
        for f in range(int(n)):
            state = step(state, make_inputs(bits[m, :, f]))
        t, v, count = title.readback(state, players)
        assert int(count) == int(want_n[m]) == n
        # XLA:CPU may contract a multiply-add; a few ulp of a plane of 5.
        assert np.abs(t - want_t[m]).max() <= 1e-5
        assert np.abs(v - want_v[m]).max() <= 1e-6


@pytest.mark.parametrize("config", sorted(_configs()))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_control_fails_the_limits_and_float32_passes(config, seed):
    import importlib

    from benchmark.inputs import HeldKeys

    cfg = _configs()[config]
    limits = {k: v["limit"] for k, v in cfg["limits"].items()}
    ref = importlib.import_module(f"benchmark.reference.{cfg['title']}_np")
    players = int(cfg["settings"]["num_players"])
    with open(os.path.join(ROOT, "benchmark", "traffic", "wan.json"),
              encoding="utf-8") as f:
        params = json.load(f)["inputs"]
    frames = 150
    bits = HeldKeys(seed, 16, players, params).table(frames)[:, :, :frames]
    n = np.full((16,), frames)
    t32, v32, _ = ref.replay(bits, n)
    again_t, again_v, _ = ref.replay(bits, n)
    tb, vb, _ = ref.replay(bits, n, precision="bfloat16")
    assert np.abs(again_t - t32).max() == 0 and np.abs(again_v - v32).max() == 0
    gap_t = float(np.abs(tb - t32).max())
    gap_v = float(np.abs(vb - v32).max())
    # The lower precision has to fail one of the numbers, not each.
    assert (gap_t > limits["reference.translation_gap"]
            or gap_v > limits["reference.velocity_gap"])
    assert gap_t > 3 * limits["reference.translation_gap"]


def test_round_bfloat16_is_round_to_nearest_even():
    import jax.numpy as jnp

    x = np.asarray([1.0, 1.00390625, 1.01171875, -2.4, 0.05, 3e-5, 0.0],
                   np.float32)
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    assert np.array_equal(ref.round_bfloat16(x), want)


def test_control_schedule_rounds_state_on_device():
    import jax

    from benchmark.titles import box_game as title
    from bevy_ggrs_tpu.schedule import make_inputs

    players, frames = 2, 60
    bits = _table(9, 1, players, frames)
    step = jax.jit(title.make_schedule("bf16_state"))
    state = title.make_world(players)
    for f in range(frames):
        state = step(state, make_inputs(bits[0, :, f]))
    t, v, _ = title.readback(state, players)
    assert np.array_equal(ref.round_bfloat16(t), t)
    want_t, want_v, _ = ref.replay(bits, np.asarray([frames]),
                                   precision="bfloat16")
    assert np.abs(t - want_t[0]).max() <= 0.05
