"""Seeded protocol fixture: size drift, enum gap, ghost allowlist member."""
import struct

_HEADER = struct.Struct("<HBBII")
HEADER_BYTES = 10


class Protocol:
    Model = 0
    Rollout = 1
    Batch = 3


TRACE_KINDS = frozenset({Protocol.Rollout, Protocol.Ghost})


class Codec:
    RAW = 0
    LZ4 = 1
    PARTS = 1


PARTS_KINDS = frozenset({Protocol.Rollout, Protocol.Phantom})
