"""Read a profiler's ``.xplane.pb`` with the standard library alone.

``jax.profiler.ProfileData`` gives each event's own stats but not the
stats of its *metadata*, and on a TPU's ``XLA Ops`` line the metadata is
where an op's JAX name stack (``tf_op``) and source line live.  This
module decodes the protocol-buffer wire format of tsl's ``xplane.proto``
directly:

    XSpace         planes = 1
    XPlane         id = 1, name = 2, lines = 3, event_metadata = 4 (map),
                   stat_metadata = 5 (map), stats = 6
    XLine          id = 1, name = 2, timestamp_ns = 3, events = 4,
                   duration_ps = 9, display_id = 10, display_name = 11
    XEvent         metadata_id = 1, offset_ps = 2, duration_ps = 3,
                   stats = 4, num_occurrences = 5
    XStat          metadata_id = 1, double = 2, uint64 = 3, int64 = 4,
                   str = 5, bytes = 6, ref = 7 (a stat metadata id whose
                   name is the value)
    XEventMetadata id = 1, name = 2, metadata = 3, display_name = 4,
                   stats = 5, child_id = 6
    XStatMetadata  id = 1, name = 2, description = 3

An event starts at its line's ``timestamp_ns`` plus its ``offset_ps``
and lasts its ``duration_ps``, each taken down to whole nanoseconds as
``ProfileData`` takes them, so both readers give the same intervals and
host spans and device ops compare directly.
"""
from __future__ import annotations

import dataclasses
import gzip
import struct


@dataclasses.dataclass
class Event:
    name: str          # the event metadata's name (an op's HLO text)
    start_ns: int
    end_ns: int
    stats: dict        # the event's own stats (decoded where asked for)
    meta: dict         # the event metadata's stats (``tf_op``, ...)

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _varint(buf, i: int):
    result = shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _fields(buf):
    """(field number, wire type, value) of each field of one message;
    a length-delimited value is a memoryview into ``buf``."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, wire, value


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: dict):
    """(name, value) of one XStat."""
    mid, value = 0, None
    for f, _, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _str(v)
        elif f == 6:
            value = bytes(v)
        elif f == 7:
            value = stat_names.get(v, "")
    return stat_names.get(mid, str(mid)), value


def _map_entry(buf):
    key, value = 0, b""
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf, event_stats) -> Plane:
    name, line_bufs, meta_bufs, stat_names = "", [], {}, {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = _str(v)
        elif f == 3:
            line_bufs.append(v)
        elif f == 4:
            k, m = _map_entry(v)
            meta_bufs[k] = m
        elif f == 5:
            k, m = _map_entry(v)
            for g, _, w in _fields(m):
                if g == 2:
                    stat_names[k] = _str(w)
    metas = {}
    for k, m in meta_bufs.items():
        mname, mstats = "", {}
        for f, _, v in _fields(m):
            if f == 2:
                mname = _str(v)
            elif f == 5:
                sname, sval = _stat(v, stat_names)
                mstats[sname] = sval
        metas[k] = (mname, mstats)
    want_stats = event_stats(name)
    lines = []
    for lb in line_bufs:
        lname, ts_ns, events = "", 0, []
        for f, _, v in _fields(lb):
            if f == 2:
                lname = _str(v)
            elif f == 3:
                ts_ns = _signed(v)
            elif f == 4:
                events.append(v)
        out = []
        for eb in events:
            mid = off = dur = 0
            stats = {}
            for f, _, v in _fields(eb):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = _signed(v)
                elif f == 3:
                    dur = _signed(v)
                elif f == 4 and want_stats:
                    sname, sval = _stat(v, stat_names)
                    stats[sname] = sval
            mname, mstats = metas.get(mid, ("", {}))
            start = ts_ns + off // 1000
            out.append(Event(mname, start, start + dur // 1000, stats,
                             mstats))
        lines.append(Line(lname, out))
    return Plane(name, lines)


def read(path: str, event_stats=lambda plane: True) -> list:
    """The planes of one ``.xplane.pb`` (or ``.xplane.pb.gz``).  ``event_stats(plane_name)``
    says whether to decode the events' own stats there (a device plane's
    hundreds of thousands of ops carry only their device clock)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        buf = memoryview(fh.read())
    return [_plane(v, event_stats) for f, _, v in _fields(buf) if f == 1]
