"""Property battery for the RPC wire framing (``repro.rpc.wire``).

The transport's correctness floor: every frame kind round-trips bitwise
(dtype + shape + bytes preserved through the zero-copy path), and every
class of malformed input — truncated header, truncated body, garbage magic,
oversize announcements, descriptor lies — is REJECTED with
:class:`FrameError` before any payload-sized allocation, never decoded into
something plausible.  Runs property-style under hypothesis when installed,
via the seeded fallback shim otherwise (tier-1 bare-container rule).
"""
from __future__ import annotations

import socket
import threading

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                          # pragma: no cover
    from _hypothesis_fallback import given, settings, st

from repro.featurestore.placement import RoutingTable
from repro.rpc import wire
from repro.rpc.wire import (ChannelClosed, FrameError, decode_frame,
                            encode_frame, pack_table, recv_frame, send_frame,
                            unpack_table)

ALL_KINDS = sorted(wire.KINDS)
DTYPES = [np.int64, np.int32, np.int16, np.int8, np.float32, np.float64,
          np.uint8, np.bool_]


def _bytes_of(frame_bufs) -> bytes:
    return b"".join(bytes(b) for b in frame_bufs)


def _roundtrip(kind, meta, arrays):
    bufs, total = encode_frame(kind, meta, arrays)
    raw = _bytes_of(bufs)
    assert len(raw) == total
    k, m, a = decode_frame(raw)
    assert k == kind
    assert m == dict(meta or {})
    assert set(a) == set(arrays or {})
    for name, arr in (arrays or {}).items():
        got = a[name]
        assert got.dtype == np.asarray(arr).dtype, name
        assert got.shape == np.ascontiguousarray(arr).shape, name
        np.testing.assert_array_equal(got, np.asarray(arr))
    return raw


# ---------------------------------------------------------------------------
# round-trip properties
# ---------------------------------------------------------------------------

def test_all_kinds_roundtrip_empty():
    for kind in ALL_KINDS:
        _roundtrip(kind, {}, {})
        _roundtrip(kind, {"x": 1, "s": "τ", "none": None, "f": 0.5,
                          "nested": {"a": [1, 2]}}, {})


@settings(max_examples=25)
@given(st.integers(0, len(ALL_KINDS) - 1),
       st.integers(0, len(DTYPES) - 1),
       st.integers(0, 3),                    # ndim
       st.integers(0, 9),                    # dim size
       st.integers(1, 4))                    # number of arrays
def test_roundtrip_dtype_shape_preserved(ki, di, ndim, dim, n_arrays):
    rng = np.random.default_rng(ki * 1000 + di * 100 + ndim * 10 + dim)
    arrays = {}
    for j in range(n_arrays):
        dt = DTYPES[(di + j) % len(DTYPES)]
        shape = tuple(int(rng.integers(0, dim + 1)) for _ in range(ndim))
        arrays[f"a{j}"] = (rng.integers(0, 2, size=shape).astype(dt)
                           if dt is np.bool_ else
                           (rng.random(size=shape) * 100).astype(dt))
    _roundtrip(ALL_KINDS[ki], {"req": ki}, arrays)


def test_roundtrip_empty_and_scalar_shapes():
    # 0-d, 0-length, and F-ordered inputs all survive (encode makes them
    # C-contiguous; shape/dtype are authoritative from the descriptor)
    _roundtrip(wire.RESULT, {}, {"s": np.float32(3.5) * np.ones(())})
    _roundtrip(wire.RESULT, {}, {"e": np.zeros((0, 4), np.int64)})
    f_ordered = np.asfortranarray(np.arange(12, np.float32(12) + 12)
                                  .reshape(3, 4))
    bufs, _ = encode_frame(wire.RESULT, {}, {"f": f_ordered})
    _, _, a = decode_frame(_bytes_of(bufs))
    np.testing.assert_array_equal(a["f"], f_ordered)


def test_zero_copy_views_on_receive():
    arr = np.arange(64, dtype=np.int64)
    raw = _bytes_of(encode_frame(wire.REQUEST, {"req": 1}, {"ids": arr})[0])
    _, _, a = decode_frame(raw)
    # the decoded array is a VIEW over the frame buffer, not a copy
    assert a["ids"].base is not None


# ---------------------------------------------------------------------------
# rejection properties
# ---------------------------------------------------------------------------

def test_unknown_kind_and_reserved_key_rejected_on_encode():
    with pytest.raises(FrameError):
        encode_frame(200, {}, {})
    with pytest.raises(FrameError):
        encode_frame(wire.HELLO, {wire._ARRAYS_KEY: []}, {})


def test_oversize_payload_rejected_on_encode(monkeypatch):
    monkeypatch.setattr(wire, "MAX_FRAME_BYTES", 1 << 10)
    with pytest.raises(FrameError):
        encode_frame(wire.REQUEST, {}, {"x": np.zeros(1 << 12, np.int8)})


@settings(max_examples=25)
@given(st.integers(0, 200))
def test_truncated_frame_rejected(cut):
    raw = _roundtrip(wire.REQUEST, {"req": 7},
                     {"ids": np.arange(17, dtype=np.int64)})
    cut = min(cut, len(raw) - 1)
    with pytest.raises(FrameError):
        decode_frame(raw[:cut])


@settings(max_examples=25)
@given(st.integers(0, 19), st.integers(0, 255))
def test_garbage_prefix_rejected(pos, val):
    raw = bytearray(_roundtrip(wire.HEARTBEAT, {"beat_age_s": 0.0}, {}))
    orig = raw[pos]
    raw[pos] = (orig + 1 + val) % 256
    if raw[pos] == orig:
        raw[pos] = (orig + 1) % 256
    with pytest.raises(FrameError):
        decode_frame(bytes(raw))


def test_admission_bounds_checked_before_allocation():
    # a header announcing a 2^60-byte payload must be refused from the
    # 20-byte prefix alone (no payload-sized allocation attempt)
    hdr = wire.HEADER.pack(wire.MAGIC, wire.REQUEST, 0, 0, 0, 1 << 60)
    with pytest.raises(FrameError, match="admission"):
        decode_frame(hdr)
    hdr = wire.HEADER.pack(wire.MAGIC, wire.REQUEST, 0, 0,
                           wire.MAX_META_BYTES + 1, 0)
    with pytest.raises(FrameError, match="admission"):
        decode_frame(hdr)


def test_descriptor_lies_rejected():
    # descriptor claims more bytes than the payload carries
    bufs, _ = encode_frame(wire.RESULT, {}, {"x": np.zeros(4, np.int64)})
    raw = bytearray(_bytes_of(bufs))
    raw2 = raw.replace(b'"<i8",[4]', b'"<i8",[9]')
    assert raw2 != raw
    with pytest.raises(FrameError):
        decode_frame(bytes(raw2))
    # trailing junk after a complete frame
    with pytest.raises(FrameError, match="trailing"):
        decode_frame(bytes(raw) + b"\x00")
    # meta that is valid JSON but not an object
    mb = b"[1,2]"
    hdr = wire.HEADER.pack(wire.MAGIC, wire.HELLO, 0, 0, len(mb), 0)
    with pytest.raises(FrameError, match="not a JSON object"):
        decode_frame(hdr + mb)


# ---------------------------------------------------------------------------
# socket IO: framing survives a real stream, EOF classes are distinct
# ---------------------------------------------------------------------------

def test_send_recv_over_socketpair():
    a, b = socket.socketpair()
    try:
        frames = [
            (wire.HELLO, {"index": 0}, {}),
            (wire.REQUEST, {"req": 1, "tenant": "t0"},
             {"ids": np.arange(33, dtype=np.int64)}),
            (wire.RESULT, {"req": 1, "status": "ok"},
             {"logits": np.random.default_rng(0)
              .normal(size=(8, 5)).astype(np.float32)}),
        ]
        sent = []

        def pump():
            for kind, meta, arrays in frames:
                sent.append(send_frame(a, kind, meta, arrays))
            a.close()                        # clean EOF at a boundary

        t = threading.Thread(target=pump)
        t.start()
        received = []
        for kind, meta, arrays in frames:
            k, m, arr, n = recv_frame(b)
            assert (k, m) == (kind, meta)
            for name in arrays:
                np.testing.assert_array_equal(arr[name], arrays[name])
            received.append(n)
        with pytest.raises(ChannelClosed):   # boundary EOF: clean close
            recv_frame(b)
        t.join(timeout=30)
        assert not t.is_alive()
        # compared once the sender is done: a frame can arrive before the
        # sender has recorded its size
        assert received == sent
    finally:
        a.close()
        b.close()


def test_mid_frame_eof_is_frame_error():
    a, b = socket.socketpair()
    try:
        bufs, _ = encode_frame(wire.REQUEST, {"req": 1},
                               {"ids": np.arange(100, dtype=np.int64)})
        raw = _bytes_of(bufs)
        a.sendall(raw[:len(raw) // 2])
        a.close()
        with pytest.raises(FrameError, match="mid-frame"):
            recv_frame(b)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# routing-table transport
# ---------------------------------------------------------------------------

def test_pack_unpack_table_roundtrip():
    t = RoutingTable(
        shard_of_node=np.array([0, 1, -1, 1, 0], dtype=np.int16),
        n_shards=2, version=7)
    meta, arrays = pack_table(t)
    raw = _bytes_of(encode_frame(wire.SWAPPED, meta, arrays)[0])
    _, m, a = decode_frame(raw)
    t2 = unpack_table(m, a)
    assert (t2.n_shards, t2.version) == (2, 7)
    np.testing.assert_array_equal(t2.shard_of_node, t.shard_of_node)
    assert t2.shard_of_node.dtype == np.int16

    meta, arrays = pack_table(None)
    assert unpack_table(meta, arrays) is None
