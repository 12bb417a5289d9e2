"""Just enough protobuf reading to join a TPU trace's operations to the name
stack (``tcdp.<phase>`` scopes) and custom-call target of their instruction.

The device events of an ``.xplane.pb`` carry only the instruction's text; the
name stack sits in the HLO protos that the profiler stores in the
``/host:metadata`` plane.  No schema module for either is installed, so this
walks the wire format with the few field numbers it needs:

    XSpace.planes=1; XPlane.name=2, .event_metadata=4 (map: key=1, value=2)
    XEventMetadata.name=2, .stats=5; XStat.bytes_value=6, .str_value=5
    HloProto.hlo_module=1; HloModuleProto.computations=3
    HloComputationProto.instructions=2
    HloInstructionProto.name=1, .opcode=2, .metadata=7, .custom_call_target=28
    OpMetadata.op_name=2
"""

from __future__ import annotations


def fields(buf: bytes):
    """Yield (field_number, wire_type, value) of one message; value is an int
    for varints and fixed types, bytes for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, shift = 0, 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, shift = 0, 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
        elif wt == 1:
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 5:
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        elif wt == 2:
            ln, shift = 0, 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            val = bytes(buf[i:i + ln])
            i += ln
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, wt, val


def _sub(buf: bytes, number: int):
    return [v for n, wt, v in fields(buf) if n == number and wt == 2]


def instructions_of_module(module: bytes) -> dict:
    """{instruction name: (op_name, custom_call_target, opcode)} of one
    serialized HloModuleProto."""
    out = {}
    for comp in _sub(module, 3):
        for ins in _sub(comp, 2):
            name = opcode = target = op_name = ""
            for n, wt, v in fields(ins):
                if wt != 2:
                    continue
                if n == 1:
                    name = v.decode("utf-8", "replace")
                elif n == 2:
                    opcode = v.decode("utf-8", "replace")
                elif n == 28:
                    target = v.decode("utf-8", "replace")
                elif n == 7:
                    for m, mwt, mv in fields(v):
                        if m == 2 and mwt == 2:
                            op_name = mv.decode("utf-8", "replace")
            if name:
                out[name] = (op_name, target, opcode)
    return out


def instructions_of_xplane(path: str) -> dict:
    """The same map, merged over every HLO proto stored in the trace."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for plane in _sub(space, 1):
        name = b"".join(_sub(plane, 2)[:1])
        if name != b"/host:metadata":
            continue
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                for stat in _sub(meta, 5):
                    for blob in _sub(stat, 6):
                        for module in _sub(blob, 1):
                            out.update(instructions_of_module(module))
    return out
