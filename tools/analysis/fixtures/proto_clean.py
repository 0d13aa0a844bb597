"""Clean protocol fixture: struct/const, enum, allowlist all consistent."""
import struct

_HEADER = struct.Struct("<HBBII")
HEADER_BYTES = 12


class Protocol:
    Model = 0
    Rollout = 1


TRACE_KINDS = frozenset({Protocol.Rollout})


class Codec:
    RAW = 0
    PARTS = 3


PARTS_KINDS = frozenset({Protocol.Model})
