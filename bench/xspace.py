"""Read a profiler trace (``.xplane.pb``) with the device ops' ``tf_op``.

``jax.profiler.ProfileData`` gives each event's name and its own stats,
but not the stats of its *metadata*, where the profiler puts ``tf_op``:
the HLO ``op_name`` of the device op, the program's JAX name stack
(``jit(f)/jit(g)/recoil.walk_gather/gather``).  This module parses the
file with ``google.protobuf`` and a descriptor written here for the part
of ``xplane.proto`` it needs (field numbers as in TSL's
``tsl/profiler/protobuf/xplane.proto``), so it imports no TensorFlow:

  XSpace.planes; XPlane.name, lines, event_metadata, stat_metadata;
  XLine.name, timestamp_ns, events; XEvent.metadata_id, offset_ps,
  duration_ps; XEventMetadata.name, stats; XStat.metadata_id, str_value,
  ref_value; XStatMetadata.name.

Unknown fields are skipped by the parser, so the subset reads any file.
"""

from __future__ import annotations

import dataclasses
import functools

OPS_LINE = "XLA Ops"
TF_OP = "tf_op"

# (message, [(field, number, label, type, type_name)]) with the labels and
# types of ``descriptor_pb2.FieldDescriptorProto``.
_OPT, _REP = 1, 3
_INT64, _UINT64, _STRING, _MESSAGE = 3, 4, 9, 11
_MESSAGES = [
    ("XSpace", [("planes", 1, _REP, _MESSAGE, "XPlane")]),
    ("XPlane", [("name", 2, _OPT, _STRING, None),
                ("lines", 3, _REP, _MESSAGE, "XLine"),
                ("event_metadata", 4, _REP, _MESSAGE,
                 "XPlane.EventMetadataEntry"),
                ("stat_metadata", 5, _REP, _MESSAGE,
                 "XPlane.StatMetadataEntry")]),
    ("XLine", [("name", 2, _OPT, _STRING, None),
               ("timestamp_ns", 3, _OPT, _INT64, None),
               ("events", 4, _REP, _MESSAGE, "XEvent")]),
    ("XEvent", [("metadata_id", 1, _OPT, _INT64, None),
                ("offset_ps", 2, _OPT, _INT64, None),
                ("duration_ps", 3, _OPT, _INT64, None)]),
    ("XStat", [("metadata_id", 1, _OPT, _INT64, None),
               ("str_value", 5, _OPT, _STRING, None),
               ("ref_value", 7, _OPT, _UINT64, None)]),
    ("XEventMetadata", [("name", 2, _OPT, _STRING, None),
                        ("stats", 5, _REP, _MESSAGE, "XStat")]),
    ("XStatMetadata", [("name", 2, _OPT, _STRING, None)]),
]
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}
_PACKAGE = "bench_xspace"


@functools.lru_cache(maxsize=1)
def _space_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    f = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                           package=_PACKAGE, syntax="proto3")

    def add_fields(msg, fields):
        for name, number, label, ftype, type_name in fields:
            fd = msg.field.add(name=name, number=number, label=label,
                               type=ftype)
            if type_name:
                fd.type_name = f".{_PACKAGE}.{type_name}"

    for name, fields in _MESSAGES:
        msg = f.message_type.add(name=name)
        add_fields(msg, fields)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                nested = msg.nested_type.add(name=entry)
                nested.options.map_entry = True
                add_fields(nested, [("key", 1, _OPT, _INT64, None),
                                    ("value", 2, _OPT, _MESSAGE, value)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


def parse(raw: bytes):
    """The ``XSpace`` message of a serialized trace."""
    space = _space_class()()
    space.ParseFromString(raw)
    return space


@dataclasses.dataclass(frozen=True)
class DeviceOp:
    plane: str
    name: str              # the HLO instruction, as ProfileData names it
    tf_op: str | None      # the op's HLO op_name, if the profiler gave one
    start_ns: float
    end_ns: float


def _tf_ops(plane) -> dict[int, str | None]:
    """Event metadata id -> its ``tf_op`` string (or None)."""
    stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
    out = {}
    for mid, meta in plane.event_metadata.items():
        out[mid] = None
        for st in meta.stats:
            if stat_names.get(st.metadata_id) != TF_OP:
                continue
            # A repeated string is stored once, as the name of a stat
            # metadata entry that ``ref_value`` points at.
            out[mid] = st.str_value or stat_names.get(st.ref_value)
    return out


def device_ops(space) -> list[DeviceOp]:
    """Every event of every device plane's ``XLA Ops`` line."""
    ops = []
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        tf_ops = _tf_ops(plane)
        names = {k: m.name for k, m in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            t0 = float(line.timestamp_ns)
            for e in line.events:
                a = t0 + e.offset_ps / 1e3
                ops.append(DeviceOp(plane.name, names.get(e.metadata_id, ""),
                                    tf_ops.get(e.metadata_id), a,
                                    a + e.duration_ps / 1e3))
    return ops
