"""Writes a small ``XSpace`` in the protobuf wire format, for the tests: the
counterpart of ``reducers/xspace.py`` (tsl ``xplane.proto``; only the fields
that reader and ``jax.profiler.ProfileData`` look at)."""

from __future__ import annotations


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(no: int, value: int) -> bytes:
    return _varint(no << 3) + _varint(value)


def _bytes(no: int, value) -> bytes:
    value = value.encode() if isinstance(value, str) else value
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def plane(name: str, lines: dict, stats: tuple = ("tf_op",)) -> bytes:
    """One XPlane.  ``lines``: {line name: [(event name, start_ns, dur_ns,
    {stat: string})]}; an event's stats go on its *metadata*, as the TPU's
    profiler puts ``tf_op``."""
    stat_id = {s: i + 1 for i, s in enumerate(stats)}
    meta_id, body = {}, _bytes(2, name)
    for i, (line, events) in enumerate(lines.items()):
        raw = _int(1, i + 1) + _bytes(2, line) + _int(3, 0)
        for ev_name, start, dur, ev_stats in events:
            key = (ev_name, tuple(sorted(ev_stats.items())))
            meta_id.setdefault(key, len(meta_id) + 1)
            raw += _bytes(4, _int(1, meta_id[key]) + _int(2, start * 1000)
                          + _int(3, dur * 1000))
        body += _bytes(3, raw)
    for (ev_name, ev_stats), mid in meta_id.items():
        meta = _int(1, mid) + _bytes(2, ev_name)
        for stat, value in ev_stats:
            meta += _bytes(5, _int(1, stat_id[stat]) + _bytes(5, value))
        body += _bytes(4, _int(1, mid) + _bytes(2, meta))
    for stat, sid in stat_id.items():
        body += _bytes(5, _int(1, sid) + _bytes(2, _int(1, sid)
                                                + _bytes(2, stat)))
    return body


def write(path, planes: list) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(b"".join(_bytes(1, p) for p in planes))
