"""Codec / protocol / transport tests (SURVEY.md §4 — codec round-trip)."""

import os
import pickle
import struct
import zlib

import numpy as np
import pytest

from tpu_rl.runtime import native
from tpu_rl.runtime.protocol import Codec, Protocol, _HEADER, decode, encode


# ------------------------------------------------------------- native codec
@pytest.mark.skipif(not native.available(), reason="no C++ toolchain")
class TestNativeCodec:
    def test_roundtrip_patterns(self):
        cases = [
            b"",
            b"a",
            b"abcd" * 1,
            os.urandom(10_000),  # incompressible
            b"\x00" * 100_000,  # highly compressible
            bytes(range(256)) * 500,
            pickle.dumps({"obs": np.random.randn(128, 5, 4).astype(np.float32)}),
        ]
        for raw in cases:
            comp = native.compress(raw)
            out = native.decompress(comp, len(raw))
            assert out == raw, f"roundtrip failed for {len(raw)}-byte input"

    def test_compressible_data_shrinks(self):
        raw = b"the quick brown fox " * 5000
        assert len(native.compress(raw)) < len(raw) // 10

    def test_corrupt_stream_rejected_not_crash(self):
        raw = b"hello world, hello world, hello world" * 100
        comp = bytearray(native.compress(raw))
        comp[5] ^= 0xFF
        try:
            out = native.decompress(bytes(comp), len(raw))
            assert len(out) == len(raw)  # may "succeed" with wrong bytes...
        except RuntimeError:
            pass  # ...or fail cleanly; must never segfault

    def test_crc32_matches_zlib(self):
        data = os.urandom(4096)
        assert native.crc32(data) == (zlib.crc32(data) & 0xFFFFFFFF)


# ---------------------------------------------------------------- protocol
class TestProtocol:
    def test_roundtrip_all_kinds(self):
        payloads = {
            Protocol.Model: {"actor": {"w": np.ones((64, 64), np.float32)}},
            Protocol.Rollout: {
                "obs": np.zeros(4, np.float32),
                "id": "abc",
                "done": False,
            },
            Protocol.Stat: 123.5,
        }
        for proto, payload in payloads.items():
            p2, out = decode(encode(proto, payload))
            assert p2 == proto
            if isinstance(payload, dict):
                assert set(out) == set(payload)
            else:
                assert out == payload

    def test_large_array_roundtrip_and_compression(self):
        arr = np.zeros((128, 5, 64), np.float32)  # compressible
        parts = encode(Protocol.Model, arr)
        assert len(parts[1]) < arr.nbytes // 4
        _, out = decode(parts)
        np.testing.assert_array_equal(out, arr)

    def test_tiny_payload_ships_raw(self):
        parts = encode(Protocol.Stat, 1.0)
        codec = parts[1][3]  # header byte 3 = codec id
        assert codec == Codec.RAW

    def test_encode_refuses_what_no_receiver_would_take(self, monkeypatch):
        """The cap every receiver enforces (peek / decode) holds on the send
        side too; below it the frame's bytes are what they were."""
        from tpu_rl.runtime import protocol

        payload = {"actor": {"w": np.arange(2048, dtype=np.float32)}, "ver": 3}
        before = encode(Protocol.Model, payload)
        raw = len(protocol.pack(payload))
        monkeypatch.setattr(protocol, "_MAX_RAW", raw)
        slack = protocol._FRAMING_SLACK  # the sender's pre-check leaves room for framing
        assert protocol.fits_frame(raw - slack) and not protocol.fits_frame(raw - slack + 1)
        assert encode(Protocol.Model, payload) == before
        proto, got = decode(before)
        assert proto == Protocol.Model and np.array_equal(got["actor"]["w"], payload["actor"]["w"])
        monkeypatch.setattr(protocol, "_MAX_RAW", raw - 1)
        with pytest.raises(ValueError, match="exceeds the frame cap"):
            encode(Protocol.Model, payload)
        with pytest.raises(ValueError, match="exceeds cap"):  # and nobody decodes it
            decode(before)

    def test_corrupt_frame_rejected(self):
        parts = encode(Protocol.Model, np.arange(1000))
        bad = bytearray(parts[1])
        bad[_HEADER.size + 8] ^= 0xFF  # flip a body byte -> crc mismatch
        with pytest.raises(ValueError, match="crc"):
            decode([parts[0], bytes(bad)])

    def test_foreign_frame_rejected(self):
        with pytest.raises(ValueError):
            decode([b"\x00", b"notaframe"])
        with pytest.raises(ValueError):
            decode([b"\x00"])

    @pytest.mark.skipif(not native.available(), reason="no C++ toolchain")
    def test_lz4_frame_decodes_without_native(self, monkeypatch):
        """Reverse interop: a frame LZ4-encoded by a native-codec peer decodes
        on a host with no toolchain via the pure-Python fallback."""
        arr = np.tile(np.arange(100, dtype=np.float32), 50)
        parts = encode(Protocol.Model, arr)
        assert parts[1][3] == Codec.LZ4
        monkeypatch.setattr(native, "LIB", None)
        _, out = decode(parts)
        np.testing.assert_array_equal(out, arr)

    def test_zlib_fallback_interop(self, monkeypatch):
        """A ZLIB frame (peer without the native codec) decodes fine here."""
        arr = np.random.randn(1000).astype(np.float32)
        monkeypatch.setattr(native, "LIB", None)
        parts = encode(Protocol.Rollout, arr)
        assert parts[1][3] in (Codec.ZLIB, Codec.RAW)
        monkeypatch.undo()
        _, out = decode(parts)
        np.testing.assert_array_equal(out, arr)


# ------------------------------------------------------------- safe serializer
class TestWireSerializer:
    """The wire body is a closed-schema serialization, not pickle — a hostile
    frame must not be able to execute code on decode (round-1 advisor
    finding)."""

    def test_roundtrip_every_supported_type(self):
        from tpu_rl.runtime.protocol import pack, unpack

        payload = {
            "none": None,
            "bools": [True, False],
            "int": -(2**40),
            "float": 3.14159,
            "str": "épisode-αβ",
            "bytes": b"\x00\xffraw",
            "tuple": (1, 2.0, "three"),
            "nested": {"params": {"w": np.random.randn(8, 8).astype(np.float32)}},
            "arrays": [
                np.arange(10, dtype=np.int32),
                np.ones((2, 3, 4), np.float64),
                np.array(True),
                np.zeros((0, 5), np.float32),  # zero-size
                np.float32(1.5),  # numpy scalar -> 0-d array
            ],
        }
        out = unpack(pack(payload))
        assert out["none"] is None
        assert out["bools"] == [True, False]
        assert out["int"] == -(2**40)
        assert out["float"] == payload["float"]
        assert out["str"] == payload["str"]
        assert out["bytes"] == payload["bytes"]
        assert out["tuple"] == payload["tuple"]
        np.testing.assert_array_equal(
            out["nested"]["params"]["w"], payload["nested"]["params"]["w"]
        )
        for got, want in zip(out["arrays"], payload["arrays"], strict=True):
            want = np.asarray(want)
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)

    def test_fortran_order_array_roundtrips(self):
        from tpu_rl.runtime.protocol import pack, unpack

        a = np.asfortranarray(np.arange(12, dtype=np.float32).reshape(3, 4))
        np.testing.assert_array_equal(unpack(pack(a)), a)

    def test_object_dtype_rejected_on_encode(self):
        from tpu_rl.runtime.protocol import pack

        with pytest.raises(ValueError, match="dtype|unsupported"):
            pack(np.array([object()], dtype=object))
        with pytest.raises(ValueError, match="unsupported|dtype"):
            pack(object())
        with pytest.raises(ValueError, match="non-str"):
            pack({1: "int-keyed"})

    def test_pickle_body_cannot_execute(self, tmp_path):
        """A frame whose body is a malicious pickle must raise, not execute."""
        import struct
        import zlib as _z

        from tpu_rl.runtime.protocol import Codec, _HEADER, _MAGIC, _VERSION

        marker = tmp_path / "pwned"

        class Evil:
            def __reduce__(self):
                return (open, (str(marker), "w"))

        evil = pickle.dumps(Evil())
        header = _HEADER.pack(
            _MAGIC, _VERSION, Codec.RAW, len(evil), _z.crc32(evil) & 0xFFFFFFFF
        )
        with pytest.raises(ValueError):
            decode([bytes([Protocol.Rollout]), header + evil])
        assert not marker.exists()

    def test_truncated_and_trailing_rejected(self):
        from tpu_rl.runtime.protocol import pack, unpack

        buf = pack({"a": np.arange(5)})
        with pytest.raises(ValueError):
            unpack(buf[:-3])
        with pytest.raises(ValueError):
            unpack(buf + b"xx")

    def test_every_reject_path_raises_valueerror_only(self):
        """Sub.recv drops frames on `except ValueError` — any other exception
        type escaping decode() crashes the role process (hostile-input DoS).
        Exercise each normalization: garbage dtype (np.dtype -> TypeError),
        corrupt zlib body (zlib.error), oversize int on encode (struct.error)."""
        import zlib as _z

        from tpu_rl.runtime.protocol import (
            Codec,
            _HEADER,
            _MAGIC,
            _VERSION,
            pack,
            unpack,
        )

        # garbage dtype string
        forged = b"a" + struct.pack("<I", 2) + b"zz"
        with pytest.raises(ValueError, match="dtype"):
            unpack(forged)

        # corrupt zlib body with valid CRC
        body = b"\xde\xad\xbe\xef" * 8
        header = _HEADER.pack(
            _MAGIC, _VERSION, Codec.ZLIB, 64, _z.crc32(body) & 0xFFFFFFFF
        )
        with pytest.raises(ValueError, match="zlib"):
            decode([bytes([Protocol.Rollout]), header + body])

        # zlib bomb: expands past declared raw_size -> size mismatch, bounded
        bomb = _z.compress(b"\x00" * 10_000_000, level=9)
        header = _HEADER.pack(
            _MAGIC, _VERSION, Codec.ZLIB, 64, _z.crc32(bomb) & 0xFFFFFFFF
        )
        with pytest.raises(ValueError, match="size mismatch"):
            decode([bytes([Protocol.Rollout]), header + bomb])

        # int outside int64 on encode
        with pytest.raises(ValueError, match="int64"):
            pack({"seed": 2**63})

    def test_oversize_shape_rejected(self):
        """A forged array header claiming a huge shape must not allocate."""
        from tpu_rl.runtime.protocol import unpack

        dt = b"<f4"
        forged = (
            b"a"
            + struct.pack("<I", len(dt))
            + dt
            + struct.pack("<I", 1)
            + struct.pack("<q", 2**50)  # claimed 1-quadrillion-row array
            + struct.pack("<I", 4)
            + b"\x00\x00\x00\x00"
        )
        with pytest.raises(ValueError):
            unpack(forged)


# ---------------------------------------------------------------- transport
class TestTransport:
    def test_pub_sub_localhost(self):
        import time

        from tpu_rl.runtime.transport import Pub, Sub

        port = 28761
        sub = Sub("127.0.0.1", port, bind=True)
        pub = Pub("127.0.0.1", port, bind=False)
        try:
            # PUB/SUB slow-joiner: ping until the subscription propagates.
            for _ in range(100):
                pub.send(Protocol.Stat, -1.0)
                if sub.recv(timeout_ms=100) is not None:
                    break
            else:
                pytest.fail("subscription never propagated")
            for i in range(5):
                pub.send(Protocol.Stat, float(i))
            got = []
            while len(got) < 5:
                msg = sub.recv(timeout_ms=2000)
                assert msg is not None
                if msg[1] >= 0:  # skip stray handshake pings
                    got.append(msg)
            assert [p for p, _ in got] == [Protocol.Stat] * 5
            assert [v for _, v in got] == [0.0, 1.0, 2.0, 3.0, 4.0]
        finally:
            pub.close()
            sub.close()

    def test_drain_nonblocking(self):
        import time

        from tpu_rl.runtime.transport import Pub, Sub

        port = 28762
        sub = Sub("127.0.0.1", port, bind=True)
        pub = Pub("127.0.0.1", port, bind=False)
        try:
            assert list(sub.drain()) == []
            # PUB/SUB slow-joiner: ping until the subscription propagates
            # (a fixed sleep is a deterministic flake on slow hosts).
            for _ in range(100):
                pub.send(Protocol.Stat, -1.0)
                if sub.recv(timeout_ms=100) is not None:
                    break
            else:
                pytest.fail("subscription never propagated")
            list(sub.drain())  # flush stray handshake pings
            pub.send(Protocol.Stat, 7.0)
            pub.send(Protocol.Stat, 8.0)
            deadline = time.time() + 10.0
            vals = []
            while len(vals) < 2 and time.time() < deadline:
                vals += [v for _, v in sub.drain() if v >= 0]
            assert vals == [7.0, 8.0]
        finally:
            pub.close()
            sub.close()
