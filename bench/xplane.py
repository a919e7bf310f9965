"""Read a profiler trace (``.xplane.pb``) with its operations' metadata.

``jax.profiler.ProfileData`` gives each event's name and times, but not
the metadata the TPU runtime attaches to an XLA operation: its
``tf_op`` (the ``jax.named_scope`` path, such as
``jit(<lambda>)/repro.lm.decode_step_paged/...``), ``hlo_category`` and
the rest.  This module declares the part of the XSpace schema the
benchmark reads (field numbers as in TSL's ``xplane.proto``) and parses
the file with ``protobuf``.
"""

from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_SCHEMA = {
    # message: [(field, number, type, label, message type)]
    "XSpace": [("planes", 1, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XPlane")],
    "XPlane": [
        ("id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("lines", 3, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XLine"),
        ("event_metadata", 4, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
         "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
         "XPlane.StatMetadataEntry"),
    ],
    "XLine": [
        ("id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("timestamp_ns", 3, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("events", 4, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XEvent"),
    ],
    "XEvent": [
        ("metadata_id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("offset_ps", 2, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("duration_ps", 3, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
    ],
    "XStat": [
        ("metadata_id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("str_value", 5, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("ref_value", 7, _F.TYPE_UINT64, _F.LABEL_OPTIONAL, None),
    ],
    "XEventMetadata": [
        ("id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("display_name", 4, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("stats", 5, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XStat"),
    ],
    "XStatMetadata": [
        ("id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
    ],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}
_PKG = "bench.xplane"


def _classes():
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                            package=_PKG, syntax="proto3")
    for name, fields in _SCHEMA.items():
        msg = fd.message_type.add(name=name)
        for fname, num, ftype, label, mtype in fields:
            f = msg.field.add(name=fname, number=num, type=ftype, label=label)
            if mtype:
                f.type_name = f".{_PKG}.{mtype}"
        if name == "XPlane":
            for entry, value in _MAPS.items():
                e = msg.nested_type.add(name=entry)
                e.options.map_entry = True
                e.field.add(name="key", number=1, type=_F.TYPE_INT64,
                            label=_F.LABEL_OPTIONAL)
                e.field.add(name="value", number=2, type=_F.TYPE_MESSAGE,
                            label=_F.LABEL_OPTIONAL,
                            type_name=f".{_PKG}.{value}")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.XSpace"))


def load(path: str):
    """The XSpace in ``path``."""
    space = _classes()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def metadata(plane) -> dict:
    """{event metadata id: (display name, {stat name: string value})}."""
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    out = {}
    for k, md in plane.event_metadata.items():
        stats = {}
        for st in md.stats:
            stats[names.get(st.metadata_id, "")] = (
                st.str_value or names.get(st.ref_value, ""))
        out[k] = (md.display_name or md.name, stats)
    return out


def events(line):
    """(start ns, end ns, metadata id) of every event of a line."""
    t0 = line.timestamp_ns
    for ev in line.events:
        a = t0 + ev.offset_ps / 1000.0
        yield a, a + ev.duration_ps / 1000.0, ev.metadata_id
