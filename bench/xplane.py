"""Read a profiler trace (``*.xplane.pb``) with each event's metadata.

``jax.profiler.ProfileData`` gives an event its metadata's name but only
the statistics set on the event itself.  On a TPU the statistics of an
XLA operation (``tf_op``, its scope path; ``long_name``; its category)
sit on the event's metadata, shared by every run of that operation, so
``ProfileData`` cannot say which scope an operation belongs to.  This
module parses the trace with the protobuf runtime and a schema built
here from ``tsl/profiler/protobuf/xplane.proto`` (the same field numbers),
and returns planes, lines and events with the interface of
``ProfileData`` (``name``, ``lines``, ``events``, ``start_ns``,
``end_ns``, ``duration_ns``, ``stats``), where an event's ``stats`` are
its metadata's statistics updated by its own.
"""
from __future__ import annotations

import functools
from typing import Dict, List

PACKAGE = "bench.xplane"

# message -> [(field, number, type, label, message type)]; types and labels
# as in descriptor.proto: 1 double, 3 int64, 4 uint64, 9 string, 11
# message, 12 bytes; 1 optional, 3 repeated
SCHEMA = {
    "XSpace": [("planes", 1, 11, 3, "XPlane"), ("errors", 2, 9, 3, None),
               ("warnings", 3, 9, 3, None), ("hostnames", 4, 9, 3, None)],
    "XPlane": [("id", 1, 3, 1, None), ("name", 2, 9, 1, None),
               ("lines", 3, 11, 3, "XLine"),
               ("event_metadata", 4, 11, 3, "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, 11, 3, "XPlane.StatMetadataEntry"),
               ("stats", 6, 11, 3, "XStat")],
    "XLine": [("id", 1, 3, 1, None), ("display_id", 10, 3, 1, None),
              ("name", 2, 9, 1, None), ("display_name", 11, 9, 1, None),
              ("timestamp_ns", 3, 3, 1, None), ("duration_ps", 9, 3, 1, None),
              ("events", 4, 11, 3, "XEvent")],
    "XEvent": [("metadata_id", 1, 3, 1, None), ("offset_ps", 2, 3, 1, None),
               ("num_occurrences", 5, 3, 1, None),
               ("duration_ps", 3, 3, 1, None), ("stats", 4, 11, 3, "XStat")],
    "XStat": [("metadata_id", 1, 3, 1, None), ("double_value", 2, 1, 1, None),
              ("uint64_value", 3, 4, 1, None), ("int64_value", 4, 3, 1, None),
              ("str_value", 5, 9, 1, None), ("bytes_value", 6, 12, 1, None),
              ("ref_value", 7, 4, 1, None)],
    "XEventMetadata": [("id", 1, 3, 1, None), ("name", 2, 9, 1, None),
                       ("display_name", 4, 9, 1, None),
                       ("metadata", 3, 12, 1, None),
                       ("stats", 5, 11, 3, "XStat"),
                       ("child_id", 6, 3, 3, None)],
    "XStatMetadata": [("id", 1, 3, 1, None), ("name", 2, 9, 1, None),
                      ("description", 3, 9, 1, None)],
}
MAPS = {"XPlane": [("EventMetadataEntry", "XEventMetadata"),
                   ("StatMetadataEntry", "XStatMetadata")]}
ONEOFS = {"XEvent": ("data", ("offset_ps", "num_occurrences")),
          "XStat": ("value", ("double_value", "uint64_value", "int64_value",
                              "str_value", "bytes_value", "ref_value"))}


@functools.lru_cache(maxsize=1)
def space_class():
    """The ``XSpace`` message class, in a descriptor pool of its own."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fd = descriptor_pb2.FileDescriptorProto(
        name="bench/xplane.proto", package=PACKAGE, syntax="proto3")

    def add_fields(msg, fields, oneof=None):
        if oneof:
            msg.oneof_decl.add(name=oneof[0])
        for name, number, typ, label, type_name in fields:
            f = msg.field.add(name=name, number=number, type=typ, label=label)
            if type_name:
                f.type_name = f".{PACKAGE}.{type_name}"
            if oneof and name in oneof[1]:
                f.oneof_index = 0

    for name, fields in SCHEMA.items():
        msg = fd.message_type.add(name=name)
        add_fields(msg, fields, ONEOFS.get(name))
        for entry, value in MAPS.get(name, ()):
            sub = msg.nested_type.add(name=entry)
            sub.options.map_entry = True
            add_fields(sub, [("key", 1, 3, 1, None),
                             ("value", 2, 11, 1, value)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{PACKAGE}.XSpace"))


class Event:
    __slots__ = ("start_ns", "end_ns", "duration_ns", "name", "stats")

    def __init__(self, start_ns, duration_ns, name, stats):
        self.start_ns, self.duration_ns = start_ns, duration_ns
        self.end_ns = start_ns + duration_ns
        self.name, self.stats = name, stats


class Line:
    """A line whose events are read when first asked for (a trace's lines
    that the reduction skips cost nothing)."""

    def __init__(self, line, meta, stat_names):
        self.name = line.name
        self._src = (line, meta, stat_names)
        self._events = None

    @property
    def events(self) -> List[Event]:
        if self._events is None:
            line, meta, stat_names = self._src
            self._events = events(line, meta, stat_names)
            self._src = None
        return self._events


class Plane:
    def __init__(self, name: str, lines: List[Line]):
        self.name, self.lines = name, lines


def _stat_value(stat, stat_names: Dict[int, str]):
    which = stat.WhichOneof("value")
    if which is None:
        return None
    if which == "ref_value":
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, which)


def _stats(stats, stat_names) -> dict:
    return {stat_names.get(s.metadata_id, str(s.metadata_id)):
            _stat_value(s, stat_names) for s in stats}


def events(line, meta, stat_names) -> List[Event]:
    out = []
    for ev in line.events:
        name, st = meta.get(ev.metadata_id, ("", {}))
        if ev.stats:
            st = dict(st, **_stats(ev.stats, stat_names))
        out.append(Event(line.timestamp_ns + ev.offset_ps * 1e-3,
                         ev.duration_ps * 1e-3, name, st.items()))
    return out


def planes(space) -> List[Plane]:
    out = []
    for p in space.planes:
        stat_names = {k: m.name for k, m in p.stat_metadata.items()}
        meta = {k: (m.name or m.display_name, _stats(m.stats, stat_names))
                for k, m in p.event_metadata.items()}
        out.append(Plane(p.name, [Line(line, meta, stat_names)
                                  for line in p.lines]))
    return out


class Profile:
    """A trace as ``planes`` (the interface ``trace_reduce`` reads)."""

    def __init__(self, data: bytes):
        space = space_class()()
        space.ParseFromString(data)
        self.planes = planes(space)

    @classmethod
    def from_file(cls, path) -> "Profile":
        with open(path, "rb") as fh:
            return cls(fh.read())
