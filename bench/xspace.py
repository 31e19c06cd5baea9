"""The profiler's XSpace protobuf, read with stats and event metadata.

``jax.profiler.ProfileData`` gives each event's name and its own stats, but
not the stats an event's metadata carries, nor the metadata id that tells
two programs' ops of one name apart.  This module declares the XSpace
schema (``tsl/profiler/protobuf/xplane.proto``) and the part of the HLO
module proto that names each instruction's op (``xla/service/hlo.proto``,
``xla/xla_data.proto``), with the fields read here, in a private
descriptor pool, so a trace is read with ``google.protobuf`` alone:
``parse`` takes a ``.xplane.pb`` file or its text proto, and
``hlo_op_names`` reads the HLO protos a TPU trace keeps per program.
"""
from __future__ import annotations

import functools

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory, text_format

_F = descriptor_pb2.FieldDescriptorProto
_SCHEMA = {   # message -> [(field, number, type, repeated, message type, oneof)]
    "XSpace": [("planes", 1, _F.TYPE_MESSAGE, True, "XPlane", None)],
    "XPlane": [("id", 1, _F.TYPE_INT64, False, None, None),
               ("name", 2, _F.TYPE_STRING, False, None, None),
               ("lines", 3, _F.TYPE_MESSAGE, True, "XLine", None),
               ("event_metadata", 4, _F.TYPE_MESSAGE, True, "XPlane.EventMetadataEntry", None),
               ("stat_metadata", 5, _F.TYPE_MESSAGE, True, "XPlane.StatMetadataEntry", None),
               ("stats", 6, _F.TYPE_MESSAGE, True, "XStat", None)],
    "XLine": [("id", 1, _F.TYPE_INT64, False, None, None),
              ("name", 2, _F.TYPE_STRING, False, None, None),
              ("timestamp_ns", 3, _F.TYPE_INT64, False, None, None),
              ("events", 4, _F.TYPE_MESSAGE, True, "XEvent", None),
              ("duration_ps", 9, _F.TYPE_INT64, False, None, None),
              ("display_id", 10, _F.TYPE_INT64, False, None, None),
              ("display_name", 11, _F.TYPE_STRING, False, None, None)],
    "XEvent": [("metadata_id", 1, _F.TYPE_INT64, False, None, None),
               ("offset_ps", 2, _F.TYPE_INT64, False, None, 0),
               ("duration_ps", 3, _F.TYPE_INT64, False, None, None),
               ("stats", 4, _F.TYPE_MESSAGE, True, "XStat", None),
               ("num_occurrences", 5, _F.TYPE_INT64, False, None, 0)],
    "XStat": [("metadata_id", 1, _F.TYPE_INT64, False, None, None),
              ("double_value", 2, _F.TYPE_DOUBLE, False, None, 0),
              ("uint64_value", 3, _F.TYPE_UINT64, False, None, 0),
              ("int64_value", 4, _F.TYPE_INT64, False, None, 0),
              ("str_value", 5, _F.TYPE_STRING, False, None, 0),
              ("bytes_value", 6, _F.TYPE_BYTES, False, None, 0),
              ("ref_value", 7, _F.TYPE_UINT64, False, None, 0)],
    "XEventMetadata": [("id", 1, _F.TYPE_INT64, False, None, None),
                       ("name", 2, _F.TYPE_STRING, False, None, None),
                       ("metadata", 3, _F.TYPE_BYTES, False, None, None),
                       ("display_name", 4, _F.TYPE_STRING, False, None, None),
                       ("stats", 5, _F.TYPE_MESSAGE, True, "XStat", None),
                       ("child_id", 6, _F.TYPE_INT64, True, None, None)],
    "XStatMetadata": [("id", 1, _F.TYPE_INT64, False, None, None),
                      ("name", 2, _F.TYPE_STRING, False, None, None),
                      ("description", 3, _F.TYPE_STRING, False, None, None)],
    "HloProto": [("hlo_module", 1, _F.TYPE_MESSAGE, False, "HloModuleProto", None)],
    "HloModuleProto": [("computations", 3, _F.TYPE_MESSAGE, True, "HloComputationProto", None)],
    "HloComputationProto": [("instructions", 2, _F.TYPE_MESSAGE, True, "HloInstructionProto",
                             None)],
    "HloInstructionProto": [("name", 1, _F.TYPE_STRING, False, None, None),
                            ("metadata", 7, _F.TYPE_MESSAGE, False, "OpMetadata", None)],
    "OpMetadata": [("op_name", 2, _F.TYPE_STRING, False, None, None)],
}
_ONEOFS = {"XEvent": "data", "XStat": "value"}
_MAPS = {"EventMetadataEntry": "XEventMetadata", "StatMetadataEntry": "XStatMetadata"}
_PACKAGE = "bench_xspace"


def _add_fields(msg, fields):
    for name, number, ftype, repeated, type_name, oneof in fields:
        f = msg.field.add(name=name, number=number, type=ftype,
                          label=_F.LABEL_REPEATED if repeated else _F.LABEL_OPTIONAL)
        if type_name:
            f.type_name = f".{_PACKAGE}.{type_name}"
        if oneof is not None:
            f.oneof_index = oneof


@functools.lru_cache(maxsize=None)
def _classes() -> dict:
    """Message name -> class, from one private descriptor pool."""
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                            package=_PACKAGE, syntax="proto3")
    for name, fields in _SCHEMA.items():
        msg = fd.message_type.add(name=name)
        if name in _ONEOFS:
            msg.oneof_decl.add(name=_ONEOFS[name])
        _add_fields(msg, fields)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                m = msg.nested_type.add(name=entry)
                m.options.map_entry = True
                _add_fields(m, [("key", 1, _F.TYPE_INT64, False, None, None),
                                ("value", 2, _F.TYPE_MESSAGE, False, value, None)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {m: message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{_PACKAGE}.{m}"))
            for m in _SCHEMA}


def parse(path: str):
    """An XSpace message from a ``.xplane.pb`` file or a ``.pbtxt`` text proto."""
    space = _classes()["XSpace"]()
    if path.endswith(".pbtxt"):
        with open(path) as f:
            text_format.Parse(f.read(), space)
    else:
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
    return space


def stat_value(stat, stat_names: dict):
    """A stat's value; a reference resolves to the name it points at."""
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    value = getattr(stat, kind)
    return stat_names.get(value, value) if kind == "ref_value" else value


def stats(items, stat_names: dict) -> dict:
    """``{stat name: value}`` of a repeated XStat field."""
    return {stat_names.get(s.metadata_id, str(s.metadata_id)): stat_value(s, stat_names)
            for s in items}


def hlo_op_names(hlo_proto: bytes) -> dict:
    """``{instruction name: op_name}`` of a serialized HLO module proto,
    for the instructions that carry an op name."""
    hlo = _classes()["HloProto"]()
    hlo.ParseFromString(hlo_proto)
    return {i.name: i.metadata.op_name
            for c in hlo.hlo_module.computations for i in c.instructions
            if i.metadata.op_name}
