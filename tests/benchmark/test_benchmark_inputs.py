"""The input generator and the network seed: stable per seed, different
across seeds, independent of how far the table was extended."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmark.inputs import HeldKeys, network_seed

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _params(mix="wan"):
    with open(os.path.join(ROOT, "benchmark", "traffic", mix + ".json"),
              encoding="utf-8") as f:
        return json.load(f)["inputs"]


def _digest(table):
    return hashlib.sha256(np.ascontiguousarray(table).tobytes()).hexdigest()


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_bytes_whatever_the_extension(seed):
    a = HeldKeys(seed, 4, 2, _params()).table(900)[:, :, :900]
    g = HeldKeys(seed, 4, 2, _params())
    for frame in (3, 200, 899):       # extended on demand, in steps
        g.bits(1, frame, 0)
    b = g.table(900)[:, :, :900]
    assert _digest(a) == _digest(b)


def test_a_known_seed_is_byte_stable():
    """A frozen digest: a change of the generator changes every cell's
    work, which only a ``benchmark`` PR may do."""
    t = HeldKeys(42, 2, 2, _params()).table(256)[:, :, :256]
    assert t.dtype == np.uint8 and t.shape == (2, 2, 256)
    assert _digest(t) == (
        "58a4fdbad5776ebe4c23e1146e53a6af60f45b911600df927df648652422b347")


def test_seeds_differ_and_streams_differ():
    a = HeldKeys(1, 2, 2, _params()).table(400)[:, :, :400]
    b = HeldKeys(2, 2, 2, _params()).table(400)[:, :, :400]
    assert _digest(a) != _digest(b)
    assert not np.array_equal(a[0, 0], a[0, 1])
    assert not np.array_equal(a[0, 0], a[1, 0])


def test_only_allowed_masks_and_held_for_frames():
    p = _params()
    t = HeldKeys(3, 64, 2, p).table(2000)[:, :, :2000]
    assert set(np.unique(t)) <= set(p["masks"])
    for m in p["masks"]:                       # no opposing pair
        assert not (m & 1 and m & 2) and not (m & 4 and m & 8)
    changes = (np.diff(t.astype(int), axis=2) != 0).mean()
    # median hold 10 frames, sigma 1: a change every ~18 frames a player
    assert 0.03 < changes < 0.09


def test_every_mix_uses_the_one_generator():
    folder = os.path.join(ROOT, "benchmark", "traffic")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name), encoding="utf-8") as f:
            mix = json.load(f)
        HeldKeys(0, 1, 2, mix["inputs"]).table(10)


def test_unknown_generator_is_refused():
    with pytest.raises(ValueError):
        HeldKeys(0, 1, 2, {"kind": "four_key_cycle"})


def test_network_seed_fits_and_differs():
    seeds = [0, 1, 2**31, 2**31 + 99]
    out = [network_seed(s) for s in seeds]
    assert all(0 <= s < 2**32 for s in out) and len(set(out)) == len(out)
    np.random.RandomState(out[-1])


def test_loopback_network_is_deterministic_per_seed():
    from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork

    def drops(seed):
        net = LoopbackNetwork(latency=0.03, jitter=0.016, loss=0.03,
                              seed=network_seed(seed))
        a, b = net.socket("a"), net.socket("b")
        got = []
        for i in range(400):
            a.send_to(bytes([i % 256]), "b")
            net.advance(1 / 60)
            got.append(len(b.receive_all()))
        return got, net.dropped

    assert drops(5) == drops(5)
    assert drops(5) != drops(6)
