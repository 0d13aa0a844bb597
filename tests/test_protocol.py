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
        parts = encode(Protocol.RolloutBatch, arr)
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

        payload = {"obs": {"w": np.arange(2048, dtype=np.float32)}, "ver": 3}
        before = encode(Protocol.RolloutBatch, payload)
        raw = len(protocol.pack(payload))
        monkeypatch.setattr(protocol, "_MAX_RAW", raw)
        slack = protocol._FRAMING_SLACK  # the sender's pre-check leaves room for framing
        assert protocol.fits_frame(raw - slack) and not protocol.fits_frame(raw - slack + 1)
        assert encode(Protocol.RolloutBatch, payload) == before
        proto, got = decode(before)
        assert proto == Protocol.RolloutBatch and np.array_equal(got["obs"]["w"], payload["obs"]["w"])
        monkeypatch.setattr(protocol, "_MAX_RAW", raw - 1)
        with pytest.raises(ValueError, match="exceeds the frame cap"):
            encode(Protocol.RolloutBatch, payload)
        with pytest.raises(ValueError, match="exceeds cap"):  # and nobody decodes it
            decode(before)

    def test_corrupt_frame_rejected(self):
        parts = encode(Protocol.Rollout, np.arange(1000))
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
        parts = encode(Protocol.RolloutBatch, arr)
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


# ------------------------------------------------------- the model broadcast
def _wire(parts):
    """The parts as a receiver gets them: bytes, one copy each."""
    return [bytes(p) for p in parts]


def _leaves(tree):
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _leaves(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from _leaves(v)
    elif isinstance(tree, (np.ndarray, np.generic)):
        yield tree


def _same(got, want):
    """Same structure, and per array leaf the same dtype, shape and bits."""
    if isinstance(want, dict):
        assert isinstance(got, dict) and list(got) == list(want)
        for k in want:
            _same(got[k], want[k])
    elif isinstance(want, (list, tuple)):
        assert type(got) is type(want) and len(got) == len(want)
        for g, w in zip(got, want, strict=True):
            _same(g, w)
    elif isinstance(want, (np.ndarray, np.generic)):
        want = np.asarray(want)
        assert isinstance(got, np.ndarray) and got.dtype == want.dtype
        assert got.shape == want.shape and got.tobytes() == want.tobytes()
        assert got.flags.writeable and got.flags.c_contiguous  # a copy of its own
    else:
        assert type(got) is type(want) and got == want


_RNG = np.random.default_rng(50)
MODEL_TREES = {
    "nested": {
        "actor": {
            "embed": {"w": _RNG.standard_normal((64, 32)).astype(np.float32)},
            "blocks": [
                {"wq": _RNG.standard_normal((32, 32)).astype(np.float32),
                 "b": np.zeros(32, np.float32)}
                for _ in range(3)
            ],
        },
        "ver": 12, "epoch": 2, "t_tx": 1_700_000_000_123_456_789,
    },
    "empty": {"actor": {}, "ver": -1, "epoch": 0, "t_tx": 0},
    "scalar-leaves": {"actor": {"lr": 3e-4, "steps": 7, "on": True, "name": "pi",
                                "none": None, "raw": b"\x00\xff"}, "ver": 1},
    "zero-d": {"actor": {"temp": np.float32(0.25), "flag": np.array(True),
                         "count": np.array(7, np.int64)}, "ver": 2},
    "non-contiguous": {"actor": {
        "f": np.asfortranarray(np.arange(12, dtype=np.float32).reshape(3, 4)),
        "strided": np.arange(40, dtype=np.int16)[::3],
        "t": np.arange(6, dtype=np.float64).reshape(2, 3).T}, "ver": 3},
    "dtypes": {"actor": {
        "i8": np.arange(-4, 4, dtype=np.int8), "u16": np.arange(9, dtype=np.uint16),
        "i32": np.arange(5, dtype=np.int32), "f16": np.linspace(0, 1, 7).astype(np.float16),
        "f64": np.linspace(-1, 1, 5), "bool": np.array([True, False, True]),
        "c64": (np.arange(4) * (1 + 2j)).astype(np.complex64),
        "big-endian": np.arange(6, dtype=">f4"), "nan": np.array([np.nan, -0.0, np.inf], np.float32)},
        "ver": 4},
    "zero-size": {"actor": {"none": np.zeros((0, 5), np.float32), "w": np.ones(3, np.float32),
                            "also": np.zeros((4, 0), np.int32)}, "ver": 5},
    "tuples-and-lists": {"actor": (np.arange(3, dtype=np.float32), [np.arange(2), (np.float32(1.0),)]),
                         "ver": 6},
    "one-leaf": {"actor": {"w": np.arange(100, dtype=np.float32)}, "ver": 7},
    "bare-array": np.arange(10, dtype=np.float32).reshape(2, 5),
}


def _model_parts(name="nested"):
    return _wire(encode(Protocol.Model, MODEL_TREES[name]))


def _forged(desc: bytes, leaves: list, total=None, codec=Codec.PARTS, crc=None):
    """A frame in the Model layout whose header is consistent with whatever
    description and leaves it is given, unless told otherwise."""
    from tpu_rl.runtime.protocol import _MAGIC, _VERSION

    running = zlib.crc32(desc)
    for v in leaves:
        running = zlib.crc32(v, running)
    header = _HEADER.pack(
        _MAGIC, _VERSION, codec,
        len(desc) + sum(len(v) for v in leaves) if total is None else total,
        (running if crc is None else crc) & 0xFFFFFFFF,
    )
    return [bytes([Protocol.Model]), header + desc, *leaves]


def _array_ref(dt: bytes, shape, nbytes, index):
    return (b"p" + struct.pack("<I", len(dt)) + dt + struct.pack("<I", len(shape))
            + b"".join(struct.pack("<q", n) for n in shape)
            + struct.pack("<II", nbytes, index))


def _reject_cases():
    """name -> (parts, message pattern): each a frame a receiver must refuse."""
    good = _model_parts()
    desc = good[1][_HEADER.size:]
    n = len(good) - 2
    cases = {}
    for i in range(n):  # one flipped byte in any leaf
        bad = list(good)
        leaf = bytearray(bad[2 + i])
        leaf[len(leaf) // 2] ^= 0x01
        bad[2 + i] = bytes(leaf)
        cases[f"flipped-byte-leaf-{i}"] = (bad, "crc")
    head = bytearray(good[1])
    head[-1] ^= 0x01  # t_tx's last byte: the description is under the crc too
    cases["flipped-byte-description"] = ([good[0], bytes(head), *good[2:]], "crc")
    cases["dropped-last-part"] = (good[:-1], "size mismatch")
    cases["dropped-first-leaf"] = (good[:2] + good[3:], "size mismatch")
    cases["extra-part"] = (good + [b"\x00" * 8], "size mismatch")
    # the same with headers that agree with the parts: the description decides
    cases["dropped-part-consistent-header"] = (_forged(desc, good[2:-1]), "out of order or missing")
    cases["extra-part-consistent-header"] = (_forged(desc, good[2:] + [b"x" * 8]), "array parts")
    cases["swapped-leaves"] = (_forged(desc, [good[3], good[2], *good[4:]]), "holds")
    cases["leaf-one-byte-short"] = (_forged(desc, [good[2][:-1], *good[3:]]), "holds")
    f4 = b"<f4"
    cases["length-disagrees-with-shape"] = (
        _forged(_array_ref(f4, (4,), 12, 0), [b"\x00" * 12]), "byte-size mismatch")
    cases["same-part-read-twice"] = (
        _forged(b"l" + struct.pack("<I", 2) + _array_ref(f4, (2,), 8, 0) * 2, [b"\x00" * 8]),
        "out of order")
    cases["part-index-out-of-range"] = (
        _forged(_array_ref(f4, (2,), 8, 5), [b"\x00" * 8]), "out of order or missing")
    cases["unknown-dtype-kind"] = (
        _forged(_array_ref(b"|O", (1,), 8, 0), [b"\x00" * 8]), "dtype")
    cases["garbage-dtype"] = (_forged(_array_ref(b"zz", (1,), 8, 0), [b"\x00" * 8]), "dtype")
    cases["negative-dim"] = (_forged(_array_ref(f4, (-2,), 8, 0), [b"\x00" * 8]), "negative")
    cases["declared-total-over-cap"] = (_forged(desc, good[2:], total=(1 << 30) + 1), "exceeds cap")
    cases["declared-total-wrong"] = (_forged(desc, good[2:], total=17), "size mismatch")
    cases["unknown-layout-mark"] = (_forged(desc, good[2:], codec=9), "malformed|unknown codec")
    cases["unknown-layout-mark-two-parts"] = (
        _forged(b"d" + struct.pack("<d", 1.5), [], codec=9), "unknown codec")
    cases["truncated-description"] = (_forged(desc[:-5], good[2:]), "truncated")
    cases["trailing-description-bytes"] = (_forged(desc + b"n", good[2:]), "trailing")
    cases["short-head"] = ([good[0], good[1][:7], *good[2:]], "short frame")
    cases["bad-magic"] = ([good[0], b"XX" + good[1][2:], *good[2:]], "magic")
    cases["parts-on-another-kind"] = ([bytes([Protocol.Rollout]), *good[1:]], "not allowed")
    return cases


class TestModelFrame:
    """``Protocol.Model`` goes out as ``[proto, head, leaf 0, ..., leaf n-1]``:
    the leaves' own buffers under one running CRC, never compressed."""

    @pytest.mark.parametrize("name", sorted(MODEL_TREES))
    def test_round_trip_same_bits_dtypes_and_structure(self, name):
        tree = MODEL_TREES[name]
        parts = encode(Protocol.Model, tree)
        n_arrays = sum(1 for _ in _leaves(tree))
        assert len(parts) == 2 + n_arrays and parts[1][3] == Codec.PARTS
        assert parts[0] == bytes([Protocol.Model])
        # the header's size field is everything behind it; its crc runs over it all
        _, _, _, raw_size, crc = _HEADER.unpack_from(parts[1])
        behind = [parts[1][_HEADER.size:], *parts[2:]]
        assert raw_size == sum(len(p) for p in behind)
        assert crc == zlib.crc32(b"".join(bytes(p) for p in behind)) & 0xFFFFFFFF
        proto, got = decode(_wire(parts))
        assert proto == Protocol.Model
        _same(got, tree)
        proto, again = decode(parts)  # and straight from the sender's views
        _same(again, tree)

    def test_a_leafs_part_is_the_leafs_own_memory(self):
        big = np.arange(1 << 18, dtype=np.float32)  # 1 MiB: over any copy threshold
        small = np.arange(4, dtype=np.float32)
        strided = np.arange(1 << 16, dtype=np.float32)[::2]  # must be made contiguous
        parts = encode(Protocol.Model, {"actor": {"big": big, "small": small, "s": strided}})
        views = [np.frombuffer(p, np.uint8) for p in parts[2:]]
        assert np.shares_memory(views[0], big) and np.shares_memory(views[1], small)
        assert not np.shares_memory(views[2], strided)
        assert views[0].nbytes == big.nbytes and isinstance(parts[2], memoryview)

    def test_the_received_tree_owns_its_arrays(self):
        parts = _model_parts()
        _, got = decode(parts)
        for leaf in _leaves(got):
            assert leaf.flags.owndata or leaf.base is not None and leaf.base.flags.owndata
            assert not any(np.shares_memory(leaf, np.frombuffer(p, np.uint8)) for p in parts[2:])

    @pytest.mark.parametrize("case", sorted(_reject_cases()))
    def test_rejected_before_the_tree_is_built(self, case, monkeypatch):
        from tpu_rl.runtime import protocol

        parts, pattern = _reject_cases()[case]

        def built(_tree):
            raise AssertionError("a rejected frame's tree was built")

        monkeypatch.setattr(protocol, "_own", built)
        with pytest.raises(ValueError, match=pattern):
            decode(parts)

    def test_the_reject_cases_start_from_a_frame_that_is_accepted(self):
        good = _model_parts()
        _same(decode(good)[1], MODEL_TREES["nested"])
        _same(decode(_forged(good[1][_HEADER.size:], good[2:]))[1], MODEL_TREES["nested"])

    def test_never_asks_the_compressor(self, monkeypatch):
        def forbidden(*_a, **_k):
            raise AssertionError("a model frame went to the compressor")

        monkeypatch.setattr(native, "compress", forbidden)
        monkeypatch.setattr(zlib, "compress", forbidden)
        tree = {"actor": {"w": np.zeros((256, 256), np.float32)}, "ver": 1}  # would compress
        parts = encode(Protocol.Model, tree)
        assert parts[1][3] == Codec.PARTS and len(parts[2]) == 256 * 256 * 4
        with pytest.raises(AssertionError):  # the other kinds still ask
            encode(Protocol.RolloutBatch, tree)

    def test_the_cap_holds_on_both_sides_and_fits_frame_keeps_its_meaning(self, monkeypatch):
        from tpu_rl.runtime import protocol

        payload = {"actor": {"w": np.arange(2048, dtype=np.float32)}, "ver": 3}
        before = _wire(encode(Protocol.Model, payload))
        total = _HEADER.unpack_from(before[1])[3]
        monkeypatch.setattr(protocol, "_MAX_RAW", total)
        slack = protocol._FRAMING_SLACK
        assert protocol.fits_frame(total - slack) and not protocol.fits_frame(total - slack + 1)
        assert _wire(encode(Protocol.Model, payload)) == before
        _same(decode(before)[1], payload)
        monkeypatch.setattr(protocol, "_MAX_RAW", total - 1)
        with pytest.raises(ValueError, match="exceeds the frame cap"):
            encode(Protocol.Model, payload)
        with pytest.raises(ValueError, match="exceeds cap"):  # and nobody decodes it
            decode(before)

    def test_no_trailer_and_no_relay(self):
        from tpu_rl.runtime.protocol import PARTS_KINDS, TRACE_KINDS, pack_trace, peek, trailer_of

        assert PARTS_KINDS == {Protocol.Model} and not PARTS_KINDS & TRACE_KINDS
        with pytest.raises(ValueError, match="not allowed"):
            encode(Protocol.Model, {"v": 1}, pack_trace(1, 2, 3, 4))
        for name in ("empty", "one-leaf", "nested"):  # 2, 3 and many parts
            with pytest.raises(ValueError):
                peek(_model_parts(name))
        one = _model_parts("one-leaf")
        assert len(one) == 3 and trailer_of(Protocol.Model, one) is None
        assert trailer_of(Protocol.Rollout, one) is one[2]

    @pytest.mark.skipif(not native.available(), reason="no C++ toolchain")
    def test_the_native_batch_validator_rejects_it_too(self):
        from tpu_rl.runtime.protocol import MAX_PROTO, TRACE_KINDS_MASK

        frames = [_model_parts("empty"), _model_parts("one-leaf"), encode(Protocol.Stat, 1.0)]
        verdicts = native.validate_batch(frames, TRACE_KINDS_MASK, MAX_PROTO)
        assert [v == 0 for v in verdicts] == [False, False, True]

    def test_an_old_layout_model_frame_still_decodes(self):
        """What a parent-commit learner sends: the generic two-part frame."""
        from tpu_rl.runtime.protocol import _MAGIC, _VERSION, pack

        raw = pack(MODEL_TREES["one-leaf"])
        header = _HEADER.pack(_MAGIC, _VERSION, Codec.RAW, len(raw), zlib.crc32(raw) & 0xFFFFFFFF)
        proto, got = decode([bytes([Protocol.Model]), header + raw])
        assert proto == Protocol.Model
        np.testing.assert_array_equal(got["actor"]["w"], MODEL_TREES["one-leaf"]["actor"]["w"])

    def test_frame_args_describe_what_went_to_the_socket(self):
        from tpu_rl.runtime.protocol import frame_args

        parts = encode(Protocol.Model, MODEL_TREES["nested"])
        assert frame_args(parts) == {
            "bytes": sum(len(p) for p in parts), "parts": len(parts), "codec": "PARTS"}
        assert frame_args(encode(Protocol.Stat, 1.0))["codec"] == "RAW"


# Every other kind's frame is what the parent commit's encode made, byte for
# byte: sha256 (first 16 hex digits) of the parts joined by "|", the part
# count and the codec, computed with that commit's protocol.py.
def _other_kinds_payloads():
    obs = np.tile(np.arange(64, dtype=np.float32), (32, 1))
    obs[:, 0] = np.arange(32)
    return {
        "Rollout": {"obs": obs[0], "act": 3, "rew": 0.5, "done": False, "id": "w1-e7"},
        "Stat": 123.5,
        "RolloutBatch": {"obs": obs, "act": np.arange(32, dtype=np.int32) % 8,
                         "rew": np.zeros(32, np.float32), "is_fir": np.zeros(32, bool),
                         "wid": 4, "seq": 99},
        "ObsRequest": {"wid": 2, "seq": 17, "obs": obs[:8], "first": np.zeros(8, bool)},
        "Act": {"seq": 17, "act": np.arange(8, dtype=np.int32), "logits": obs[:8, :8] * 0.5,
                "log_prob": -np.ones(8, np.float32)},
        "Telemetry": {"role": "worker", "counters": {"ticks": 10, "frames": 320},
                      "gauges": {"rss": 1.5e8}, "hist": {"rtt": [0.001, 0.002, 0.004] * 20}},
    }


PINNED = {
    ("Rollout", False): ("941f8920a36127fa", 2, 1),
    ("Rollout", True): ("d2778ceaa834c0b6", 3, 1),
    ("Stat", False): ("16ae3d52102d605e", 2, 0),
    ("RolloutBatch", False): ("ae52ee6641031ba4", 2, 1),
    ("RolloutBatch", True): ("a63f97205a7c5016", 3, 1),
    ("ObsRequest", False): ("5c427130e8e1bff4", 2, 1),
    ("Act", False): ("85d0fa8cdbee9e03", 2, 1),
    ("Telemetry", False): ("b67dbaa735009d47", 2, 1),
}


@pytest.mark.parametrize("kind,traced", sorted(PINNED))
def test_every_other_kind_is_byte_for_byte_the_parents_frame(kind, traced, monkeypatch):
    import hashlib

    from tpu_rl.runtime.protocol import _MAGIC, _VERSION, pack, pack_trace

    payload = _other_kinds_payloads()[kind]
    trace = pack_trace(3, 7, 0x300000007, 1234567890) if traced else None
    digest, n_parts, codec = PINNED[kind, traced]
    if native.available() or codec == Codec.RAW:
        parts = encode(Protocol[kind], payload, trace)
        assert all(type(p) is bytes for p in parts)
        got = (hashlib.sha256(b"|".join(parts)).hexdigest()[:16], len(parts), parts[1][3])
        assert got == (digest, n_parts, codec)
    # and without the native codec: the zlib frame, rebuilt here by hand
    monkeypatch.setattr(native, "LIB", None)
    raw = pack(payload)
    body = zlib.compress(raw, 1) if len(raw) >= 128 else raw
    codec = Codec.ZLIB if len(raw) >= 128 and len(body) < len(raw) else Codec.RAW
    body = body if codec == Codec.ZLIB else raw
    want = [bytes([Protocol[kind]]),
            _HEADER.pack(_MAGIC, _VERSION, codec, len(raw), zlib.crc32(body) & 0xFFFFFFFF) + body]
    assert encode(Protocol[kind], payload, trace) == want + ([trace] if traced else [])


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
