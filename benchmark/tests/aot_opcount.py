"""Count what a cell's compiled train step launches, here, with no chip: the
device operations executed per step under each ``tcdp.*`` scope, the Pallas
calls, and the memory the compiler assigned.  A change to the sync engine
whose counts equal the parent's never reached the compiled step, and is not
worth chip time.  Counts only; a time comes from a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_opcount.py resnet50_imagenet topk_lw_staged [hlo.txt]

The compile is ``aot_compile.py``'s own (importing it runs it, for ``v5e:2x2``,
and leaves the optimized HLO in ``text``).  An operation is one instruction of
the entry computation or of a computation it reaches through ``while``
(body, times the trip count: the one integer constant its condition compares
the counter with), ``call`` or ``conditional`` (every branch): a fusion, a
custom call, a copy, a collective, a sort.  What launches nothing is left
out: parameters, constants, tuples and their elements, bitcasts.  An
instruction belongs to the innermost ``tcdp.<scope>`` of its own ``op_name``,
else to its caller's; the compiler's own copies and prefetches carry no name
and stay under ``(none)``.
"""

import collections
import json
import re
import sys

import aot_compile

_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "after-all", "partition-id", "replica-id", "opt-barrier"}
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = .*?[\])}] ([a-z][a-z\-]*)\(")
_SCOPE = re.compile(r"tcdp\.([a-z_]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_BOUND = re.compile(r" = s32\[\]\S* constant\((\d+)\)")
_CONDITION = re.compile(r"condition=%?([\w.\-]+)")
_CALLED = re.compile(r"(?:body|to_apply|branch_computations|true_computation|"
                     r"false_computation)=\{?([%\w.\-, ]+)\}?")


def parse(text: str):
    """({computation: [(opcode, scope, [(callee, times), ...]), ...]}, entry)."""
    comps, bounds, entry, cur = {}, collections.defaultdict(list), None, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
            continue
        if cur is not None and _BOUND.search(line):
            bounds[id(cur)].append(int(_BOUND.search(line).group(1)))
        found = _INSTRUCTION.match(line) if cur is not None else None
        if not found:
            continue
        opcode = found.group(1)
        name = _OP_NAME.search(line)
        scopes = _SCOPE.findall(name.group(1)) if name else []
        callees = []
        if opcode in ("while", "call", "conditional"):
            # a while's condition is defined before it: its bound is known
            cond = _CONDITION.search(line)
            bound = bounds[id(comps.get(cond.group(1)))] if cond else []
            times = bound[0] if len(bound) == 1 else 1
            for group in _CALLED.findall(line):
                callees += [(c.strip().lstrip("%"), times) for c in group.split(",")]
        cur.append((opcode, scopes[-1] if scopes else "", callees))
    return comps, entry


def count(comps, entry):
    """{scope: operations executed a step}, and the same inside while loops."""
    total, looped = collections.Counter(), collections.Counter()

    def walk(comp, times, scope, in_loop):
        for opcode, own, callees in comps.get(comp, ()):
            where = own or scope
            if callees:
                for callee, n in callees:
                    walk(callee, times * n, where, in_loop or opcode == "while")
            elif opcode not in _FREE:
                total[where] += times
                if in_loop:
                    looped[where] += times

    walk(entry, 1, "", False)
    return total, looped


def main():
    text, mem = aot_compile.text, aot_compile.mem
    if len(sys.argv) > 3:
        with open(sys.argv[3], "w") as f:
            f.write(text)
    comps, entry = parse(text)
    total, looped = count(comps, entry)
    sync = [s for s in total if s not in ("", "grad", "update")]
    print("opcount " + json.dumps({
        "config": sys.argv[1], "traffic": sys.argv[2],
        "ops_by_scope": {s or "(none)": total[s] for s in sorted(total)},
        "ops_sync_scopes": sum(total[s] for s in sync),
        "ops_sync_in_while": sum(looped[s] for s in sync),
        "ops_step": sum(total.values()),
        "while_loops": sum(op == "while" for c in comps.values() for op, *_ in c),
        "pallas_calls": text.count("tpu_custom_call"),
        "temporaries_gb": round(mem.temp_size_in_bytes / 1e9, 3),
        "arguments_gb": round(mem.argument_size_in_bytes / 1e9, 3),
        "hlo_text_mb": round(len(text) / 1e6, 1)}))


if __name__ == "__main__":
    main()
