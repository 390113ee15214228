"""Wire-protocol robustness: decode() must never raise on untrusted bytes.

The UDP socket delivers attacker-controlled datagrams straight into
``protocol.decode`` (survey §2.4 boundary); the contract is None for
garbage, never an exception. Seeded fuzz over random bytes, truncated valid
messages, and bit-flipped valid messages; plus encode/decode round-trip
equality for every message type.
"""

import struct
import zlib

import numpy as np
import pytest

from bevy_ggrs_tpu.session import protocol as proto


def _valid_messages():
    return [
        proto.SyncRequest(nonce=0xDEADBEEF),
        proto.SyncReply(nonce=1),
        proto.InputMsg(handle=2, start_frame=100, payload=b"\x01\x02\x03",
                       num=3, ack_frame=99, sender_frame=103, advantage=-2),
        proto.InputAck(handle=0, ack_frame=-1),
        proto.QualityReport(send_time_ms=123456, frame_advantage=7),
        proto.QualityReply(pong_time_ms=999),
        proto.KeepAlive(),
        proto.ChecksumReport(frame=64, checksum=0xFFFFFFFF),
    ]


def test_round_trip_every_type():
    for msg in _valid_messages():
        got = proto.decode(proto.encode(msg))
        assert got == msg, (msg, got)


def test_random_bytes_never_raise():
    rng = np.random.RandomState(0)
    for _ in range(2000):
        n = int(rng.randint(0, 64))
        data = rng.bytes(n)
        proto.decode(data)  # must not raise; None or a Message both fine


def test_truncations_never_raise():
    for msg in _valid_messages():
        wire = proto.encode(msg)
        for cut in range(len(wire)):
            proto.decode(wire[:cut])


def test_bit_flips_never_raise():
    rng = np.random.RandomState(1)
    for msg in _valid_messages():
        wire = bytearray(proto.encode(msg))
        for _ in range(50):
            flipped = bytearray(wire)
            i = int(rng.randint(0, len(flipped)))
            flipped[i] ^= 1 << int(rng.randint(0, 8))
            proto.decode(bytes(flipped))


def test_wrong_magic_and_version_rejected():
    wire = bytearray(proto.encode(proto.KeepAlive()))
    bad_magic = bytes([wire[0] ^ 0xFF]) + bytes(wire[1:])
    assert proto.decode(bad_magic) is None
    bad_version = bytes([wire[0], wire[1] + 1]) + bytes(wire[2:])
    assert proto.decode(bad_version) is None


# ---------------------------------------------------------------------------
# An InputMsg parsed in place: decode_input() and decode() sort every
# datagram alike, and a session's poll comes out the same by either path


def _input_datagram(rng, size=None):
    size = int(rng.randint(0, 5)) if size is None else size
    num = int(rng.randint(0, 9))
    msg = proto.InputMsg(
        handle=int(rng.randint(0, 4)),
        start_frame=int(rng.randint(0, 1 << 20)),
        payload=rng.bytes(num * size),
        num=num,
        ack_frame=int(rng.randint(-1, 1 << 20)),
        sender_frame=int(rng.randint(-1, 1 << 20)),
        advantage=int(rng.randint(-40, 40)),
    )
    return msg, proto.encode(msg)


def _resealed(body):
    """``body`` under a trailer that verifies: what a buggy sender makes."""
    return body + struct.pack("<I", zlib.crc32(body))


def _flip(rng, wire):
    out = bytearray(wire)
    out[int(rng.randint(0, len(out)))] ^= 1 << int(rng.randint(0, 8))
    return bytes(out)


MUTATIONS = {
    "intact": lambda rng, wire: wire,
    "truncated": lambda rng, wire: wire[: int(rng.randint(0, len(wire)))],
    "bit_flip": _flip,
    "wrong_crc": lambda rng, wire: wire[:-4] + bytes(
        [wire[-4] ^ 0x5A]) + wire[-3:],
    "wrong_version": lambda rng, wire: _resealed(
        wire[:1] + bytes([proto.VERSION + 1]) + wire[2:-4]),
    "wrong_magic": lambda rng, wire: _resealed(b"\x00" + wire[1:-4]),
    "other_type": lambda rng, wire: _resealed(
        wire[:2] + bytes([proto.T_INPUT_ACK]) + wire[3:-4]),
    "trailing_garbage": lambda rng, wire: wire + rng.bytes(
        int(rng.randint(1, 6))),
    # A trailer that verifies over a body the header overstates, or that
    # stops inside the header: only a broken sender makes these.
    "short_payload": lambda rng, wire: _resealed(
        wire[: max(3, len(wire) - 4 - int(rng.randint(1, 4)))]),
    "header_only": lambda rng, wire: _resealed(
        wire[: int(rng.randint(3, proto._INPUT_HEAD.size))]),
    "random_bytes": lambda rng, wire: rng.bytes(int(rng.randint(0, 64))),
}


@pytest.mark.parametrize("mutation", list(MUTATIONS))
def test_decode_input_sorts_a_datagram_as_decode_does(mutation):
    rng = np.random.RandomState(sum(map(ord, mutation)))
    taken = 0
    for _ in range(400):
        _, wire = _input_datagram(rng)
        data = MUTATIONS[mutation](rng, wire)
        msg = proto.decode(data)
        got = proto.decode_input(data)
        if isinstance(msg, proto.InputMsg):
            taken += 1
            assert got == (
                msg.handle, msg.start_frame, msg.num, msg.payload,
                msg.ack_frame, msg.sender_frame, msg.advantage,
            ), data
        else:
            assert got is None, data
    # Each mutation does what its name says.
    if mutation == "intact":
        assert taken == 400
    elif mutation == "short_payload":
        assert 100 < taken < 400  # a cut that reaches the header is dropped
    else:
        assert taken == 0


def test_encode_input_is_encode_of_the_message():
    rng = np.random.RandomState(5)
    for _ in range(300):
        msg, wire = _input_datagram(rng)
        for payload in (msg.payload, bytearray(msg.payload)):
            assert proto.encode_input(
                msg.handle, msg.start_frame, msg.num, payload,
                msg.ack_frame, msg.sender_frame, msg.advantage,
            ) == wire


def test_input_header_offsets_come_from_the_structs():
    assert proto._INPUT_HEAD.size == proto._HDR.size + proto.InputMsg._FMT.size
    _, wire = _input_datagram(np.random.RandomState(2), size=3)
    assert proto._INPUT_HEAD.unpack_from(wire)[3:] == (
        proto.InputMsg._FMT.unpack_from(wire, proto._HDR.size))


def _polled_pair(monkeypatch, decoded_only, mutation, seed):
    """A duel over a lossy loopback in which peer 1's address also sends
    peer 0 mutated input datagrams all through play. With ``decoded_only``
    the poll's direct path is shut (``decode_input`` finds nothing), which
    is the poll as it was before PR 56. Returns everything peer 0 shows."""
    from bevy_ggrs_tpu.schedule import InputSpec
    from bevy_ggrs_tpu.session import (
        PlayerType, PredictionThreshold, SessionBuilder, SessionState,
    )
    from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
    from bevy_ggrs_tpu.utils.metrics import Metrics

    decoded = [0]
    real_decode = proto.decode

    def counting_decode(data):
        decoded[0] += 1
        return real_decode(data)

    monkeypatch.setattr(proto, "decode", counting_decode)
    if decoded_only:
        monkeypatch.setattr(proto, "decode_input", lambda data: None)
    dt = 1.0 / 60.0
    net = LoopbackNetwork(latency=2 * dt, jitter=dt, loss=0.03, seed=seed)
    sessions = []
    for me in range(2):
        b = SessionBuilder(InputSpec()).with_num_players(2)
        for h in range(2):
            b.add_player(
                PlayerType.local() if h == me else PlayerType.remote((0, h)), h)
        sessions.append(b.start_p2p_session(
            net.socket((0, me)), clock=lambda: net.now, metrics=Metrics()))
    rng = np.random.RandomState(seed)
    sock, ep = sessions[0].socket, sessions[0]._endpoints[(0, 1)]
    shown, unknown = [], 0
    for tick in range(240):
        net.advance(dt)
        if tick >= 60:
            frame = sessions[1].current_frame
            msg = proto.InputMsg(
                1, max(0, frame - int(rng.randint(0, 12))),
                rng.bytes(3), 3, int(rng.randint(-1, frame + 40)),
                int(rng.randint(-1, frame + 40)), int(rng.randint(-9, 9)))
            sock._inbox.append(
                ((0, 1), MUTATIONS[mutation](rng, proto.encode(msg))))
            if tick % 7 == 0:
                sock._inbox.append(((9, 9), proto.encode(msg)))
                unknown += 1
        for me, s in enumerate(sessions):
            s.poll_remote_clients()
            if s.current_state() == SessionState.RUNNING:
                s.add_local_input(me, np.uint8(rng.randint(0, 9)))
                try:
                    s.advance_frame()
                except PredictionThreshold:
                    pass
        shown.append((
            [(e.kind, e.addr, repr(e.data)) for e in sessions[0].events()],
            sessions[0].current_frame, sessions[0]._confirmed,
            list(sessions[0]._last_confirmed), ep.remote_frame,
            ep.remote_advantage, dict(ep._last_ack_rx), dict(ep._max_sent),
            ep.data_crc_drops, ep.version_mismatches,
            ep.refill_range(0), sessions[0]._tracker.first_incorrect,
        ))
    counters = dict(sessions[0].metrics.counters)
    direct = counters.pop("datagrams_in_direct", 0)
    peer1 = dict(sessions[1].metrics.counters)
    direct += peer1.get("datagrams_in_direct", 0)
    received = counters["datagrams_in"] + peer1["datagrams_in"]
    peer1.pop("datagrams_in_direct", None)
    monkeypatch.undo()
    return dict(shown=shown, counters=(counters, peer1), direct=direct,
                decoded=decoded[0], received=received, unknown=unknown)


@pytest.mark.parametrize("mutation", list(MUTATIONS))
def test_a_poll_comes_out_the_same_by_either_path(mutation, monkeypatch):
    seed = 100 + sum(map(ord, mutation))
    direct = _polled_pair(monkeypatch, False, mutation, seed)
    decoded = _polled_pair(monkeypatch, True, mutation, seed)
    assert direct["shown"] == decoded["shown"]
    assert direct["counters"] == decoded["counters"]
    # The mutation reached the counter it is named for.
    crc_drops = direct["shown"][-1][8]
    if mutation in ("wrong_crc", "trailing_garbage"):
        assert crc_drops == 180
    if mutation == "wrong_version":
        assert direct["shown"][-1][9] == 180
    # Every datagram of a known peer is parsed in place or decoded, never
    # both; with the direct path shut all of them are decoded.
    assert direct["direct"] > 300
    assert direct["direct"] + direct["decoded"] + direct["unknown"] == (
        direct["received"])
    assert decoded["direct"] == 0
    assert decoded["decoded"] + decoded["unknown"] == decoded["received"]
