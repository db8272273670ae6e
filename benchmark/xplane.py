"""Read a profiler trace (`.xplane.pb`) with nothing but Python: a small
decoder of the protobuf wire format for the XSpace message (tsl's
`xplane.proto`), because the source line and HLO category of a device
operation sit in its event METADATA, which `jax.profiler.ProfileData`
does not hand out.

  XSpace   1 planes*
  XPlane   2 name, 3 lines*, 4 event_metadata (map id -> XEventMetadata),
           5 stat_metadata (map id -> XStatMetadata)
  XLine    2 name, 3 timestamp_ns, 4 events*
  XEvent   1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats*
  XStat    1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str, 6 bytes,
           7 ref (a stat_metadata id whose name is the value)
  XEventMetadata  1 id, 2 name, 4 display_name, 5 stats*
  XStatMetadata   1 id, 2 name
"""

from __future__ import annotations

import struct


def _varint(buf, i):
    shift, out = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """[(field number, wire type, value)] of one message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val = bytes(buf[i:i + 8])
            i += 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 5:
            val = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wire} is not in an xplane file")
        yield num, wire, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names):
    """(name, value) of one XStat."""
    name, value = None, None
    for num, _, val in fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num == 3:
            value = val
        elif num == 4:
            value = _signed(val)
        elif num in (5, 6):
            value = bytes(val).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val, str(val))
    return name, value


def _map_entry(buf):
    key, value = None, None
    for num, _, val in fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def read_planes(path: str, want_plane, want_line) -> list:
    """[{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns,
    stats], ...]}]}] for the planes and lines the two predicates keep
    (`want_line(plane name, line name)`). An event's stats are its
    metadata's stats overlaid with its own."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, _, pbuf in fields(space):
        if num != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for pnum, _, val in fields(pbuf):
            if pnum == 2:
                name = bytes(val).decode()
            elif pnum == 3:
                lines.append(val)
            elif pnum == 4:
                metas.append(val)
            elif pnum == 5:
                key, sm = _map_entry(val)
                for snum, _, sval in fields(sm):
                    if snum == 2:
                        stat_names[key] = bytes(sval).decode()
        if not want_plane(name):
            continue
        meta = {}
        for entry in metas:
            key, em = _map_entry(entry)
            ename, stats = "", {}
            for mnum, _, mval in fields(em):
                if mnum == 2:
                    ename = bytes(mval).decode("utf-8", "replace")
                elif mnum == 5:
                    k, v = _stat(mval, stat_names)
                    stats[k] = v
            meta[key] = (ename, stats)
        out_lines = []
        for lbuf in lines:
            lname, t0_ns, events = "", 0, []
            for lnum, _, val in fields(lbuf):
                if lnum == 2:
                    lname = bytes(val).decode()
                elif lnum == 3:
                    t0_ns = _signed(val)
                elif lnum == 4:
                    events.append(val)
            if not want_line(name, lname):
                continue
            evs = []
            for ebuf in events:
                mid, off_ps, dur_ps, own = 0, 0, 0, None
                for enum, _, val in fields(ebuf):
                    if enum == 1:
                        mid = val
                    elif enum == 2:
                        off_ps = _signed(val)
                    elif enum == 3:
                        dur_ps = _signed(val)
                    elif enum == 4:
                        own = own or {}
                        k, v = _stat(val, stat_names)
                        own[k] = v
                ename, stats = meta.get(mid, (str(mid), {}))
                if own:
                    stats = {**stats, **own}
                evs.append([ename, t0_ns + off_ps * 1e-3, dur_ps * 1e-3,
                            stats])
            out_lines.append({"name": lname, "events": evs})
        planes.append({"name": name, "lines": out_lines})
    return planes
