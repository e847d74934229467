"""Device ops of a profiler trace picked by an op-name scope: the name a
nested ``jax.jit`` or ``jax.named_scope`` writes into the ``op_name`` of
its ops' HLO metadata.  XLA fuses and renames ops, so the device trace's op
names (``fusion.3``, ``sort.2``) do not say which function they ran; the
trace keeps every program's HLO on its ``/host:metadata`` plane, and the
``XLA Modules`` line of a device plane says which program each op ran in.

``jax.profiler.ProfileData`` shows neither the metadata plane nor event
metadata stats, so the XSpace is read here with a minimal protobuf schema
of the fields used (field numbers from ``tsl/profiler/protobuf/
xplane.proto`` and ``xla/service/hlo.proto``; maps as repeated entries).

    python chipbench/hloscope.py <trace dir> <scope>

prints the device time of the scope's ops by op name.
"""

from __future__ import annotations

import bisect
import re
import sys

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

import devtrace

MODULES_LINE = "XLA Modules"
# message: [(field, number, type), ...]; a type is a scalar kind or a
# message name, "*" marks a repeated field
_SCHEMA = {
    "XSpace": [("planes", 1, "*XPlane")],
    "XPlane": [("name", 2, "string"), ("lines", 3, "*XLine"),
               ("event_metadata", 4, "*EventMetadataEntry"),
               ("stat_metadata", 5, "*StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64"),
                           ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "*XEvent")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64"), ("stats", 4, "*XStat")],
    "XStat": [("metadata_id", 1, "int64"), ("uint64_value", 3, "uint64"),
              ("int64_value", 4, "int64"), ("str_value", 5, "string"),
              ("bytes_value", 6, "bytes"), ("ref_value", 7, "uint64")],
    "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                       ("display_name", 4, "string"), ("stats", 5, "*XStat")],
    "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
    "HloProto": [("hlo_module", 1, "HloModuleProto")],
    "HloModuleProto": [("name", 1, "string"),
                       ("computations", 3, "*HloComputationProto"),
                       ("id", 5, "int64")],
    "HloComputationProto": [("instructions", 2, "*HloInstructionProto")],
    "HloInstructionProto": [("name", 1, "string"),
                            ("metadata", 7, "OpMetadata")],
    "OpMetadata": [("op_name", 2, "string")],
}
_PACKAGE = "chipbench_trace"


def _classes() -> dict:
    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="chipbench_trace.proto",
                                            package=_PACKAGE, syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, num, kind in fields:
            rep = kind.startswith("*")
            kind = kind.lstrip("*")
            f = m.field.add(name=name, number=num,
                            label=T.LABEL_REPEATED if rep
                            else T.LABEL_OPTIONAL)
            if kind in _SCHEMA:
                f.type, f.type_name = T.TYPE_MESSAGE, f".{_PACKAGE}.{kind}"
            else:
                f.type = getattr(T, "TYPE_" + kind.upper())
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {msg: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.{msg}")) for msg in _SCHEMA}


_CLASSES: dict = {}


def message(name: str):
    """A new message of the schema's type ``name``."""
    if not _CLASSES:
        _CLASSES.update(_classes())
    return _CLASSES[name]()


def _program(name: str) -> str:
    """``jit_f(12)`` and ``jit_f`` with program id 12 share the key "12"."""
    m = re.search(r"\((\d+)\)\s*$", name)
    return m.group(1) if m else name


def scoped_instructions(space, scope: str) -> dict:
    """{program: {HLO instruction names whose op_name holds ``scope``}}
    over the programs on the trace's metadata plane, keyed by program id
    and by module name (the union over the module's programs)."""
    out = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        for e in plane.event_metadata:
            for st in e.value.stats:
                if not st.bytes_value:
                    continue
                hlo = message("HloProto")
                hlo.ParseFromString(st.bytes_value)
                names = {i.name for c in hlo.hlo_module.computations
                         for i in c.instructions
                         if scope in i.metadata.op_name}
                # the entry's key is the program id; its name the module's,
                # with the id or without
                name = e.value.name
                out[str(e.key)] = out[_program(name)] = names
                # programs of one function, where an op names no id
                out.setdefault(name.split("(")[0], set()).update(names)
    return out


def scoped_ops(space, scope: str, lo: float, hi: float) -> list:
    """``[[op name, start_ns, dur_ns], ...]`` of the ops starting inside
    [lo, hi] on the first device plane (by name) with ops, the plane the
    harness reads, that ran an instruction whose op_name holds ``scope``:
    named so by a stat of the op (its ``tf_op``, where the profiler writes
    one), or by its program's HLO, the program being the op's
    ``program_id`` stat or else the ``XLA Modules`` span it lies in."""
    dev = sorted((p for p in space.planes if p.name.startswith("/device:")
                  and "CPU" not in p.name and any(
                      line.name == devtrace.DEVICE_LINE and line.events
                      for line in p.lines)), key=lambda p: p.name)
    if not dev:
        return []
    plane = dev[0]
    scoped = scoped_instructions(space, scope)
    md = {e.key: e.value for e in plane.event_metadata}
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}

    def stats(ev):
        for st in list(ev.stats) + list(md[ev.metadata_id].stats
                                        if ev.metadata_id in md else []):
            name = stat_names.get(st.metadata_id, "")
            val = (stat_names.get(st.ref_value, "") if st.ref_value
                   else st.str_value or st.int64_value or st.uint64_value)
            yield name, val

    def events(line):
        for ev in line.events:
            start = line.timestamp_ns + ev.offset_ps * 1e-3
            yield ev, start, ev.duration_ps * 1e-3

    mods = sorted((s, s + d, str(dict(stats(ev)).get(
        "program_id") or _program(md[ev.metadata_id].name)))
                  for line in plane.lines if line.name == MODULES_LINE
                  for ev, s, d in events(line) if ev.metadata_id in md)
    starts = [m[0] for m in mods]
    out = []
    for line in plane.lines:
        if line.name != devtrace.DEVICE_LINE:
            continue
        for ev, s, d in events(line):
            if not lo <= s <= hi or ev.metadata_id not in md:
                continue
            text = md[ev.metadata_id].name
            st = dict(stats(ev))
            hit = scope in text or any(isinstance(v, str) and scope in v
                                       for v in st.values())
            if not hit:
                prog = st.get("program_id")
                if prog is None:
                    i = bisect.bisect_right(starts, s) - 1
                    prog = mods[i][2] if i >= 0 and s < mods[i][1] else None
                hit = devtrace.op_name(text) in scoped.get(str(prog), ())
            if hit:
                out.append([devtrace.op_name(text), s, d])
    return out


def load(path: str):
    """The XSpace of one ``.xplane.pb``."""
    space = message("XSpace")
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


if __name__ == "__main__":
    import hostspans

    path = hostspans.newest(sys.argv[1])
    ops = scoped_ops(load(path), sys.argv[2], float("-inf"), float("inf"))
    print(devtrace.top_ops(ops, n=50))
