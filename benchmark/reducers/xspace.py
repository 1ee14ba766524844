"""A small reader of the profiler's ``.xplane.pb`` for what
``jax.profiler.ProfileData`` does not hand out: an event's *metadata* stats.

On a TPU the trace gives an executed HLO operation's name as its whole HLO
text and keeps what the framework knows about it — ``tf_op`` (the
``metadata.op_name`` path with the ``jax.named_scope`` names in it),
``hlo_category``, ``flops``, ``bytes_accessed`` — as stats of the event's
``XEventMetadata``, which ``ProfileData``'s events do not expose.  No
``xplane_pb2`` is installed here, so this reads the protobuf wire format
directly, and only the few messages it needs (tsl ``xplane.proto``):

    XSpace.planes=1 / XPlane.name=2 .lines=3 .event_metadata=4 (map)
    .stat_metadata=5 (map) / XLine.name=2 .timestamp_ns=3 .events=4 /
    XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3 /
    XEventMetadata.id=1 .name=2 .stats=5 / XStatMetadata.name=2 /
    XStat.metadata_id=1 .str_value=5 .ref_value=7
"""

from __future__ import annotations

KEPT_STATS = ("tf_op",)     # the string stats of an event's metadata kept

def fields(buf):
    """(field number, wire type, value) of one message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0 or wire == 2:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if wire == 2:
                yield key >> 3, 2, buf[i:i + v]
                i += v
            else:
                yield key >> 3, 0, v
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            yield key >> 3, wire, buf[i:i + width]
            i += width
        else:
            raise ValueError(f"wire type {wire} is not in an XSpace")


def _map_entry(buf):
    key = value = None
    for no, _w, v in fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _name(buf) -> str:
    for no, _w, v in fields(buf):
        if no == 2:
            return bytes(v).decode("utf-8", "replace")
    return ""


def _event_metadata(buf, stat_names: dict) -> dict:
    out = {"name": ""}
    for no, _w, v in fields(buf):
        if no == 2:
            out["name"] = bytes(v).decode("utf-8", "replace")
        elif no == 5:
            stat = value = None
            for sno, _sw, sv in fields(v):
                if sno == 1:
                    stat = stat_names.get(sv)
                elif sno == 5:
                    value = bytes(sv).decode("utf-8", "replace")
                elif sno == 7:
                    value = stat_names.get(sv)
            if stat in KEPT_STATS and value is not None:
                out[stat] = value
    return out


def _events(buf, t0_ps: int) -> list:
    """[(metadata_id, start_ns, dur_ns)] of one line."""
    out = []
    for no, _w, v in fields(buf):
        if no != 4:
            continue
        meta = off = dur = 0
        for eno, ew, ev in fields(v):
            if ew:
                continue
            if eno == 1:
                meta = ev
            elif eno == 2:
                off = ev
            elif eno == 3:
                dur = ev
        out.append((meta, (t0_ps + off) / 1e3, dur / 1e3))
    return out


def read(path, plane_rx, lines=()) -> dict:
    """{plane: {'metadata': {id: {'name', 'tf_op' if it has one}}, 'lines':
    {line: [(metadata_id, start_ns, dur_ns)]}}} of the planes whose name
    matches ``plane_rx``; events only of the lines named in ``lines``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for no, _w, plane in fields(space):
        if no != 1 or not plane_rx.match(_name(plane)):
            continue
        stat_names, metas, raw_lines = {}, [], []
        for pno, _pw, v in fields(plane):
            if pno == 5:
                key, value = _map_entry(v)
                stat_names[key] = _name(value)
            elif pno == 4:
                metas.append(_map_entry(v))
            elif pno == 3:
                raw_lines.append(v)
        found = {}
        for line in raw_lines:
            name = _name(line)
            if name in lines:
                t0 = next((v for no2, w2, v in fields(line)
                           if no2 == 3 and w2 == 0), 0)
                found[name] = _events(line, t0 * 1000)
        out[_name(plane)] = {
            "metadata": {k: _event_metadata(v, stat_names)
                         for k, v in metas},
            "lines": found}
    return out
