"""The part of an `.xplane.pb` that `jax.profiler.ProfileData` does not
hand out: the metadata of a plane's events. On the TPU an op event's own
stats are its timing; the op's `op_name` (the path of `jax.named_scope`s it
was traced under) stands among the stats of the event's METADATA
(`XPlane.event_metadata[id].stats`), which the Python reader skips. This
reads just that, from the protobuf wire format (tensorflow/tsl
`xplane.proto`; no generated module is imported: the process holds the
chip for JAX, and the benchmark adds no dependency):

    XSpace.planes = 1;  XPlane.name = 2, .event_metadata = 4 (map: key 1,
    value 2);  XEventMetadata.name = 2, .stats = 5;  XStat.str_value = 5
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, at
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; length-delimited
    values come back as bytes."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 1:
            value, at = buf[at:at + 8], at + 8
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield number, kind, value


def event_metadata_text(path: str, plane_name: str) -> Dict[str, str]:
    """{an event's name: every string stat of its metadata, joined} for the
    plane `plane_name` (empty where the file has no such plane)."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, str] = {}
    for number, kind, plane in fields(space):
        if number != 1 or kind != 2:
            continue
        parts = list(fields(plane))
        name = next((v for n, k, v in parts if n == 2 and k == 2), b"")
        if name.decode("utf-8", "replace") != plane_name:
            continue
        for n, k, entry in parts:
            if n != 4 or k != 2:
                continue
            meta = next((v for en, ek, v in fields(entry) if en == 2 and ek == 2), b"")
            event, text = "", []
            for mn, mk, mv in fields(meta):
                if mn == 2 and mk == 2:
                    event = mv.decode("utf-8", "replace")
                elif mn == 5 and mk == 2:
                    text += [sv.decode("utf-8", "replace")
                             for sn, sk, sv in fields(mv) if sn == 5 and sk == 2]
            if event:
                out[event] = " ".join(text)
    return out
