"""Cut a capture down to what the reductions read, small enough to commit as
a test's data: of the first chip's plane the ``XLA Modules`` / ``XLA Ops``
lines, of the host plane the ``tpu_rl/*`` events, and the ``Task Environment``
plane — all of it inside a few consecutive executions of the step program.

    python3 benchmarks/cut_capture.py <dir-or-xplane.pb> <out.xplane.pb.gz> [updates] [skip]

``updates`` whole periods are kept (default 7: a loss-log read-back falls in
every 5), after ``skip`` executions (default 2). Events are copied byte for
byte; only what is left out changes (of an op's metadata, every stat but the
two the reduction reads: ``tf_op`` and ``hlo_category``).
"""

from __future__ import annotations

import gzip
import os
import sys

if not __package__:  # run as a script: the checkout is not on the path yet
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import hostplane, trace  # noqa: E402
from benchmarks.trace import _fields, _map_entry, _varint  # noqa: E402


def _raw_fields(b: bytes):
    """(field number, the field's bytes as they stand, its value) of one message."""
    i, n = 0, len(b)
    while i < n:
        at = i
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v = b[i : i + size]
            i += size
        else:
            size = 8 if wire == 1 else 4
            v = b[i : i + size]
            i += size
        yield key >> 3, b[at:i], v


def _encode_varint(x: int) -> bytes:
    out = bytearray()
    while True:
        c = x & 0x7F
        x >>= 7
        out.append(c | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _delimited(field_no: int, payload: bytes) -> bytes:
    return _encode_varint(field_no << 3 | 2) + _encode_varint(len(payload)) + payload


def _line(raw: bytes, keep) -> tuple[bytes, int]:
    """The XLine with only the events ``keep(metadata_id, start_ns, end_ns)``
    accepts, and how many those are."""
    t0 = next((v for f, _, v in _raw_fields(raw) if f == 3), 0)
    out, n = [], 0
    for f, chunk, v in _raw_fields(raw):
        if f != 4:
            out.append(chunk)
            continue
        ev = dict(_fields(v))  # XEvent: metadata_id=1, offset_ps=2, duration_ps=3
        start = t0 + ev.get(2, 0) / 1e3
        if keep(ev.get(1, 0), start, start + ev.get(3, 0) / 1e3):
            out.append(chunk)
            n += 1
    return b"".join(out), n


def cut(data: bytes, updates: int = 7, skip: int = 2) -> bytes:
    tr = trace.read(data)
    dev = tr.devices[0]
    runs = sorted((m for m in dev.modules if m.name == dev.step_name), key=lambda m: m.start)
    # one execution before the window opens (the reduction sets the first it
    # sees aside) and the whole of the one that closes it
    first, last = runs[skip], runs[skip + updates + 1]
    period = (last.start - first.start) / (updates + 1)
    lo, hi = first.start - 1e3, last.end + 1e3
    planes = []
    for f, chunk, plane in _raw_fields(data):
        if f != 1:
            continue
        name = next((v.decode() for g, _, v in _raw_fields(plane) if g == 2), "")
        if name == hostplane.ENVIRONMENT_PLANE:
            planes.append(chunk)
        elif name == dev.name:
            read = set()  # the two stats of an op's metadata the reduction reads
            for g, _, v in _raw_fields(plane):
                if g == 5:
                    key, value = _map_entry(v)
                    if dict(_fields(value)).get(2) in (b"tf_op", b"hlo_category"):
                        read.add(key)
            out = []
            for g, raw, v in _raw_fields(plane):
                if g == 3:
                    lname = next((x.decode() for h, _, x in _raw_fields(v) if h == 2), "")
                    if lname in (trace.OPS_LINE, trace.MODULES_LINE):
                        line, _ = _line(v, lambda _mid, s, e: e > lo and s < hi)
                        out.append(_delimited(3, line))
                elif g == 4:  # map entry: key=1, value=2 (XEventMetadata, stats=5)
                    key, value = _map_entry(v)
                    kept = b"".join(
                        chunk for h, chunk, x in _raw_fields(value)
                        if h != 5 or dict(_fields(x)).get(1) in read
                    )
                    out.append(_delimited(4, _encode_varint(1 << 3) + _encode_varint(key)
                                          + _delimited(2, kept)))
                else:
                    out.append(raw)
            planes.append(_delimited(1, b"".join(out)))
        elif name == hostplane.HOST_PLANE:
            ours = set()
            for g, _, v in _raw_fields(plane):
                if g == 4:
                    key, value = _map_entry(v)
                    full = next((x for h, x in _fields(value) if h == 2), b"")
                    if full.startswith(hostplane.PREFIX.encode()):
                        ours.add(key)
            out = []
            for g, raw, v in _raw_fields(plane):
                if g == 3:
                    # a dispatch precedes its execution by up to two periods
                    line, n = _line(
                        v, lambda mid, s, e: mid in ours and e > lo - 2 * period and s < hi
                    )
                    if n:
                        out.append(_delimited(3, line))
                elif g == 4:
                    if _map_entry(v)[0] in ours:
                        out.append(raw)
                else:
                    out.append(raw)
            planes.append(_delimited(1, b"".join(out)))
    return b"".join(planes)


if __name__ == "__main__":
    if len(sys.argv) < 3:
        sys.exit(__doc__)
    src = trace.first_xplane(sys.argv[1])
    with (gzip.open if src.endswith(".gz") else open)(src, "rb") as fh:
        small = cut(fh.read(), *(int(a) for a in sys.argv[3:5]))
    with gzip.open(sys.argv[2], "wb", compresslevel=9) as fh:
        fh.write(small)
    print(f"{sys.argv[2]}: {len(small)} bytes before gzip")
