"""CoAP layer + lossy-link simulation tests."""
import numpy as np
import pytest

from repro.core.fastpath import ScatterPayload
from repro.transport.coap import (
    COAP_MAX_PAYLOAD,
    IEEE802154_MTU,
    LOWPAN_OVERHEAD,
    BlockReceiveRing,
    BlockwiseFraming,
    CoapMessage,
    Code,
    Option,
    Type,
    block_option_value,
    blockwise_messages,
    iter_blockwise_messages,
    transfer_stats,
)
from repro.transport.network import LossyLink, iter_tagged_frames


def test_coap_roundtrip():
    msg = CoapMessage(Type.CON, Code.POST, mid=0x1234, token=b"\xaa\xbb",
                      options=[(Option.URI_PATH, b"fl"),
                               (Option.URI_PATH, b"model"),
                               (Option.CONTENT_FORMAT, b"\x3c")],
                      payload=b"hello-cbor")
    back = CoapMessage.decode(msg.encode())
    assert back.mtype == Type.CON and back.code == Code.POST
    assert back.mid == 0x1234 and back.token == b"\xaa\xbb"
    assert back.options == sorted(msg.options)
    assert back.payload == b"hello-cbor"


def test_option_delta_extended():
    # option numbers forcing 13/14 extended deltas
    msg = CoapMessage(Type.NON, Code.GET, 1, b"", options=[(300, b"x"), (11, b"a")])
    back = CoapMessage.decode(msg.encode())
    assert back.options == [(11, b"a"), (300, b"x")]


def test_block_option_value():
    assert block_option_value(0, False, 0) == b""   # all-zero -> empty option
    assert block_option_value(0, False, 2) == bytes([0x02])
    assert block_option_value(1, True, 2) == bytes([0x1A])
    assert block_option_value(300, False, 2) == (300 << 4 | 2).to_bytes(2, "big")


@pytest.mark.parametrize("size", [0, 1, 63, 64, 65, 1000, 20027])
def test_blockwise_fits_mtu(size):
    payload = bytes(size % 251 for _ in range(size))
    msgs = blockwise_messages(payload, uri="fl/model")
    assert b"".join(m.payload for m in msgs) == payload
    for m in msgs:
        assert len(m.encode()) + LOWPAN_OVERHEAD <= IEEE802154_MTU


def test_small_message_single_frame():
    """Paper §VI-B2: FL_Local_DataSet_Update (<=28 B) always fits one frame."""
    stats = transfer_stats(b"\x00" * 28, uri="fl/progress", code=Code.CONTENT)
    assert stats.frames == 1


def test_large_model_frame_count():
    """20 kB model -> blockwise, ~payload/64 frames."""
    stats = transfer_stats(b"\x01" * 20027, uri="fl/model")
    assert stats.messages == 1
    assert stats.frames == stats.blocks == -(-20027 // COAP_MAX_PAYLOAD)
    assert stats.wire_bytes > stats.payload_bytes  # header overhead counted


def test_lossy_link_retransmits_deterministically():
    a = LossyLink(drop_prob=0.2, seed=42).send_payload(
        b"\x02" * 5000, uri="fl/model")
    b = LossyLink(drop_prob=0.2, seed=42).send_payload(
        b"\x02" * 5000, uri="fl/model")
    assert a.retransmissions == b.retransmissions > 0
    assert a.frames == a.blocks + a.retransmissions
    assert a.failed_messages == 0


def test_link_gives_up_after_max_retransmits():
    link = LossyLink(drop_prob=0.95, seed=1)
    stats = link.send_payload(b"\x02" * 500, uri="fl/model")
    assert stats.failed_messages == 1


def test_lossless_link_no_retries():
    s = LossyLink(drop_prob=0.0).send_payload(b"\x03" * 1000, uri="fl/model")
    assert s.retransmissions == 0
    assert LossyLink.airtime_seconds(s) > 0


def test_send_stream_aggregates_and_accepts_memoryviews():
    payloads = [memoryview(bytes([i]) * 300) for i in range(4)]
    stats = LossyLink(drop_prob=0.0).send_stream(payloads, uri="fl/model")
    assert stats.messages == 4
    assert stats.payload_bytes == 1200
    assert stats.frames == stats.blocks == 4 * -(-300 // COAP_MAX_PAYLOAD)
    assert stats.failed_messages == 0


def test_send_stream_stops_on_failure():
    link = LossyLink(drop_prob=0.95, seed=1)
    stats = link.send_stream([b"\x02" * 500] * 10, uri="fl/model")
    assert stats.failed_messages == 1
    assert stats.messages < 10  # aborted at the first undeliverable payload


# -- framing by arithmetic: tagged frames against built messages --------------


def _bytes(size: int) -> bytes:
    return (bytes(range(251)) * (size // 251 + 1))[:size]


def _scatter(size: int) -> ScatterPayload:
    data = _bytes(size)
    cuts = [0, 7, 100, 101, 1000, size]
    return ScatterPayload([memoryview(data)[a:b]
                           for a, b in zip(cuts, cuts[1:])])


# (payload, uri): the NUMs whose Block1 value changes width (15/16,
# 4095/4096) as the last block (M clear) and inside a run (M set)
FRAMING_CASES = [
    pytest.param(b"", "fl/model", id="empty"),
    pytest.param(_bytes(28), "fl/progress", id="single-block"),
    pytest.param(_bytes(64), "fl/model", id="one-full-block"),
    pytest.param(_bytes(16 * 64), "fl/model", id="last-num-15"),
    pytest.param(_bytes(17 * 64 - 5), "fl/model", id="last-num-16-short"),
    pytest.param(_bytes(4096 * 64), "fl/model/upload", id="last-num-4095"),
    pytest.param(_bytes(4097 * 64 + 10), "fl/model/upload",
                 id="num-4096-more-last-4097-short"),
    pytest.param(_bytes(300), "/fl/model/chunk/fb/",
                 id="multi-segment-uri"),
    pytest.param(_scatter(1500), "fl/model/upload", id="scatter-payload"),
]


@pytest.mark.parametrize("payload,uri", FRAMING_CASES)
def test_tagged_frame_builds_the_blockwise_message(payload, uri):
    frames = list(iter_tagged_frames([payload], uri=uri, client=3,
                                     window=1, indices=[5]))
    msgs = list(iter_blockwise_messages(payload, uri=uri))
    assert len(frames) == len(msgs) == max(1, -(-len(payload) // 64))
    last = len(frames) - 1
    for num, (frame, msg) in enumerate(zip(frames, msgs)):
        wire = msg.encode()
        assert (frame.client, frame.window, frame.chunk_index,
                frame.block_num, frame.more) == (3, 1, 5, num, num < last)
        assert frame.msg.encode() == wire
        assert len(wire) == frame.wire_bytes
        # the encoded frame itself says the same NUM and M
        back = CoapMessage.decode(wire)
        assert back.payload == bytes(frame.payload) == msg.payload
        block1 = [v for n, v in back.options if n == Option.BLOCK1]
        if last == 0:
            assert block1 == []
        else:
            v = int.from_bytes(block1[0], "big")
            assert (v >> 4, bool(v & 8), v & 7) == (num, num < last, 2)


def test_frame_length_by_hand():
    # header 4, token 1, "fl" 1+2, "model" 1+5, content-format 1+1,
    # Block1 1+1+1 (NUM 0, M set), marker 1, block 64
    assert BlockwiseFraming(uri="fl/model").wire_bytes(0, True, 64) == 84
    # NUM 16: a 2-byte Block1 value; last block, no marker for 0 bytes
    assert BlockwiseFraming(uri="fl/model").wire_bytes(16, False, 0) == 20
    # one block: no Block1 option at all
    assert BlockwiseFraming(uri="fl/model").wire_bytes(0, False, 28) == 45


@pytest.mark.parametrize("order", ["in-order", "reversed"])
@pytest.mark.parametrize("payload,uri", FRAMING_CASES)
def test_ring_from_tags_matches_ring_from_messages(payload, uri, order):
    frames = list(iter_tagged_frames([payload], uri=uri, client=0,
                                     window=0))
    msgs = list(iter_blockwise_messages(payload, uri=uri))
    if order == "reversed":
        frames, msgs = frames[::-1], msgs[::-1]
    by_tag, by_msg = BlockReceiveRing(), BlockReceiveRing()
    for frame, msg in zip(frames, msgs):
        by_tag.add_block(frame.payload, num=frame.block_num,
                         last=not frame.more)
        by_msg.feed(msg)
    assert by_tag.complete and by_msg.complete
    assert ([bytes(s) for s in by_tag.segments()]
            == [bytes(s) for s in by_msg.segments()])
    assert by_tag.tobytes() == bytes(payload[:len(payload)])
