"""The few message types of a profiler trace that the reduction reads,
declared here so that reading a 200 MB `.xplane.pb` needs nothing but
`google.protobuf` (upb parses it in a second or two; `jax.profiler.ProfileData`
copies every event's whole HLO text into a Python string and takes minutes
on a trace of four million ops). Field numbers are those of
tsl/profiler/protobuf/xplane.proto and xla/service/hlo.proto; fields left out
are skipped by the parser.
"""

from __future__ import annotations

from typing import Any, Dict

_INT64, _UINT64, _DOUBLE, _STRING, _BYTES, _MESSAGE = 3, 4, 1, 9, 12, 11
_OPTIONAL, _REPEATED = 1, 3

_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", _REPEATED)],
    "XPlane": [
        ("id", 1, _INT64, _OPTIONAL), ("name", 2, _STRING, _OPTIONAL),
        ("lines", 3, "XLine", _REPEATED),
        ("event_metadata", 4, "EventMetadataEntry", _REPEATED),
        ("stat_metadata", 5, "StatMetadataEntry", _REPEATED),
        ("stats", 6, "XStat", _REPEATED),
    ],
    "EventMetadataEntry": [("key", 1, _INT64, _OPTIONAL), ("value", 2, "XEventMetadata", _OPTIONAL)],
    "StatMetadataEntry": [("key", 1, _INT64, _OPTIONAL), ("value", 2, "XStatMetadata", _OPTIONAL)],
    "XLine": [
        ("id", 1, _INT64, _OPTIONAL), ("name", 2, _STRING, _OPTIONAL),
        ("timestamp_ns", 3, _INT64, _OPTIONAL), ("events", 4, "XEvent", _REPEATED),
    ],
    "XEvent": [
        ("metadata_id", 1, _INT64, _OPTIONAL), ("offset_ps", 2, _INT64, _OPTIONAL),
        ("duration_ps", 3, _INT64, _OPTIONAL),
    ],
    "XEventMetadata": [
        ("id", 1, _INT64, _OPTIONAL), ("name", 2, _STRING, _OPTIONAL),
        ("display_name", 4, _STRING, _OPTIONAL), ("stats", 5, "XStat", _REPEATED),
    ],
    "XStat": [
        ("metadata_id", 1, _INT64, _OPTIONAL), ("double_value", 2, _DOUBLE, _OPTIONAL),
        ("uint64_value", 3, _UINT64, _OPTIONAL), ("int64_value", 4, _INT64, _OPTIONAL),
        ("str_value", 5, _STRING, _OPTIONAL), ("bytes_value", 6, _BYTES, _OPTIONAL),
        ("ref_value", 7, _UINT64, _OPTIONAL),
    ],
    "XStatMetadata": [("id", 1, _INT64, _OPTIONAL), ("name", 2, _STRING, _OPTIONAL)],
    # xla/service/hlo.proto, xla/xla_data.proto: instruction names and the
    # framework path (`op_name`: the jit and named scopes around the op).
    "HloProto": [("hlo_module", 1, "HloModuleProto", _OPTIONAL)],
    "HloModuleProto": [
        ("name", 1, _STRING, _OPTIONAL), ("computations", 3, "HloComputationProto", _REPEATED),
    ],
    "HloComputationProto": [
        ("name", 1, _STRING, _OPTIONAL), ("instructions", 2, "HloInstructionProto", _REPEATED),
    ],
    "HloInstructionProto": [
        ("name", 1, _STRING, _OPTIONAL), ("opcode", 2, _STRING, _OPTIONAL),
        ("metadata", 7, "OpMetadata", _OPTIONAL),
    ],
    "OpMetadata": [("op_type", 1, _STRING, _OPTIONAL), ("op_name", 2, _STRING, _OPTIONAL)],
}

_CLASSES: Dict[str, Any] = {}


def messages() -> Dict[str, Any]:
    """{message name: class}, built once."""
    if _CLASSES:
        return _CLASSES
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    package = "stoix_bench_xplane"
    file_proto = descriptor_pb2.FileDescriptorProto(
        name="stoix_bench_xplane.proto", package=package, syntax="proto3"
    )
    for message_name, fields in _SCHEMA.items():
        message = file_proto.message_type.add(name=message_name)
        for name, number, kind, label in fields:
            field = message.field.add(name=name, number=number, label=label)
            if isinstance(kind, str):
                field.type = _MESSAGE
                field.type_name = f".{package}.{kind}"
            else:
                field.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file_proto)
    for message_name in _SCHEMA:
        _CLASSES[message_name] = message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"{package}.{message_name}")
        )
    return _CLASSES


def parse(message_name: str, data: bytes) -> Any:
    message = messages()[message_name]()
    message.ParseFromString(data)
    return message
