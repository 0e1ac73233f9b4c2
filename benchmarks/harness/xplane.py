"""A reader of the profiler's ``xplane.pb`` that needs nothing but the
``protobuf`` runtime: the few messages of XLA's ``xplane.proto`` declared
here by hand (field numbers as in tsl/profiler/protobuf/xplane.proto).

``jax.profiler.ProfileData`` reads events and their own stats, but not the
stats kept on an event's *metadata*, and that is where a TPU trace says what
kind of operation an event is (``hlo_category``: "convolution fusion", "loop
fusion", "all-reduce", ...). A fusion's name says only what its root ops are.
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto
_FIELDS = {
    "XStat": [("metadata_id", 1, _T.TYPE_INT64), ("double_value", 2, _T.TYPE_DOUBLE),
              ("uint64_value", 3, _T.TYPE_UINT64), ("int64_value", 4, _T.TYPE_INT64),
              ("str_value", 5, _T.TYPE_STRING), ("bytes_value", 6, _T.TYPE_BYTES),
              ("ref_value", 7, _T.TYPE_UINT64)],
    "XEvent": [("metadata_id", 1, _T.TYPE_INT64), ("offset_ps", 2, _T.TYPE_INT64),
               ("num_occurrences", 5, _T.TYPE_INT64), ("duration_ps", 3, _T.TYPE_INT64),
               ("stats", 4, "XStat*")],
    "XLine": [("id", 1, _T.TYPE_INT64), ("display_id", 10, _T.TYPE_INT64),
              ("name", 2, _T.TYPE_STRING), ("display_name", 11, _T.TYPE_STRING),
              ("timestamp_ns", 3, _T.TYPE_INT64), ("duration_ps", 9, _T.TYPE_INT64),
              ("events", 4, "XEvent*")],
    "XEventMetadata": [("id", 1, _T.TYPE_INT64), ("name", 2, _T.TYPE_STRING),
                       ("display_name", 4, _T.TYPE_STRING), ("metadata", 3, _T.TYPE_BYTES),
                       ("stats", 5, "XStat*"), ("child_id", 6, "int64*")],
    "XStatMetadata": [("id", 1, _T.TYPE_INT64), ("name", 2, _T.TYPE_STRING),
                      ("description", 3, _T.TYPE_STRING)],
    "EventMetadataEntry": [("key", 1, _T.TYPE_INT64), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _T.TYPE_INT64), ("value", 2, "XStatMetadata")],
    "XPlane": [("id", 1, _T.TYPE_INT64), ("name", 2, _T.TYPE_STRING),
               ("lines", 3, "XLine*"), ("event_metadata", 4, "EventMetadataEntry*"),
               ("stat_metadata", 5, "StatMetadataEntry*"), ("stats", 6, "XStat*")],
    "XSpace": [("planes", 1, "XPlane*"), ("errors", 2, "string*"),
               ("warnings", 3, "string*"), ("hostnames", 4, "string*")],
}
_PACKAGE = "bench.xplane"


def _build():
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=_PACKAGE, syntax="proto3")
    for msg, fields in _FIELDS.items():
        m = fd.message_type.add(name=msg)
        for name, number, kind in fields:
            f = m.field.add(name=name, number=number,
                            label=_T.LABEL_OPTIONAL)
            if isinstance(kind, str):
                if kind.endswith("*"):
                    f.label, kind = _T.LABEL_REPEATED, kind[:-1]
                if kind == "string":
                    f.type = _T.TYPE_STRING
                elif kind == "int64":
                    f.type = _T.TYPE_INT64
                else:
                    f.type = _T.TYPE_MESSAGE
                    f.type_name = f".{_PACKAGE}.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


_XSpace = None


def parse(path: str):
    """The trace as an XSpace message (``planes`` -> ``lines`` -> ``events``);
    the two metadata maps are lists of key/value entries."""
    global _XSpace
    if _XSpace is None:
        _XSpace = _build()
    space = _XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_value(stat):
    for field in ("str_value", "int64_value", "uint64_value", "double_value",
                  "ref_value"):
        v = getattr(stat, field)
        if v:
            return v
    return 0


def plane_tables(plane) -> Tuple[Dict[int, object], Dict[int, str]]:
    return ({e.key: e.value for e in plane.event_metadata},
            {e.key: e.value.name for e in plane.stat_metadata})


def events(plane, line) -> Iterator[Tuple[float, float, object]]:
    """(start ns, end ns, XEvent) of a line, on the trace's clock."""
    base = line.timestamp_ns
    for ev in line.events:
        start = base + ev.offset_ps / 1e3
        yield start, start + ev.duration_ps / 1e3, ev
