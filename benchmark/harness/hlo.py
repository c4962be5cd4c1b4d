"""What a compiled step's HLO text says: the bytes its collectives move
over each mesh axis, and what kind of work each instruction is.

The byte parser is a copy of ``horovod_tpu.parallel.gspmd
.collective_axis_bytes_from_hlo`` (the yardstick may not move with the
program). The instruction table is the benchmark's own: the device trace
names events after HLO instructions, and the table says whether
``fusion.123`` is a matrix multiplication, a Pallas kernel, a collective
or something else.
"""

import re

import numpy as np

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all",
                  "collective-broadcast")

# ---- bytes over each mesh axis -----------------------------------------
# `%name = f32[128,256]{1,0} all-reduce(...)`: result type, then the op.
# Async pairs: the `-start` carries the op and is counted, the `-done`
# never matches (the regexes want `(` right after the optional `-start`).
# A variadic result is a tuple; an async `-start` tuple is (inputs...,
# outputs...), so only its output half is summed.
_OPS = "|".join(re.escape(op) for op in COLLECTIVE_OPS)
_RESULT_RE = re.compile(
    r"=\s*([a-z][a-z0-9]*)\[([0-9,]*)\][^=]*?\b(" + _OPS + r")(-start)?\(")
_TUPLE_RE = re.compile(r"=\s*\(.*?\)\s*(" + _OPS + r")(-start)?\(")
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_EXPLICIT_GROUPS_RE = re.compile(
    r"(?:replica_groups|source_target_pairs)=\{(\{[0-9, {}]*\})\}")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def _shape_bytes(dtype, dims):
    itemsize = _ITEMSIZE.get(dtype)
    if itemsize is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * itemsize


def _line_collective_bytes(line):
    """``(op, nbytes)`` when the line is a counted collective, else None."""
    m = _RESULT_RE.search(line)
    if m:
        return m.group(3), _shape_bytes(m.group(1), m.group(2))
    t = _TUPLE_RE.search(line)
    if not t:
        return None
    shapes = _SHAPE_RE.findall(line[:t.end(1)])
    if t.group(2):
        # collective-permute-start carries trailing rank-0 integer handles
        while (len(shapes) > 2 and shapes[-1][1] == ""
               and shapes[-1][0] in ("u32", "s32", "u64", "s64")):
            shapes = shapes[:-1]
        half = len(shapes) // 2
        shapes = (shapes[half:] if half and not len(shapes) % 2
                  else shapes[-1:])
    return t.group(1), sum(_shape_bytes(d, dims) for d, dims in shapes)


def _device_groups(line):
    m = _EXPLICIT_GROUPS_RE.search(line)
    if m:
        return [[int(x) for x in grp.split(",") if x.strip()]
                for grp in re.findall(r"\{([0-9, ]*)\}", m.group(1))]
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims)))
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.reshape(dims).transpose(perm).reshape(-1)
        return ids.reshape(n_groups, group_size).tolist()
    return None


def _group_axes(groups, mesh_shape, axis_names):
    varies = [False] * len(mesh_shape)
    for grp in groups:
        coords = [np.unravel_index(d, mesh_shape) for d in grp]
        for ax in range(len(mesh_shape)):
            if len({c[ax] for c in coords}) > 1:
                varies[ax] = True
    return tuple(a for a, v in zip(axis_names, varies) if v)


def collective_axis_bytes(hlo_text, mesh_shape, axis_names):
    """``{axes: {"calls", "bytes", "ops": {op: bytes}}}`` of one compiled
    module. ``axes`` is the ``+``-joined mesh axes a collective's device
    groups span; ``"replica"`` collects those whose groups never leave
    one device (what a one-chip program's collectives compile to).
    ``bytes`` is the per-device result payload."""
    out = {}
    for line in hlo_text.splitlines():
        hit = _line_collective_bytes(line)
        if hit is None:
            continue
        op, nbytes = hit
        groups = _device_groups(line)
        axes = _group_axes(groups, mesh_shape, axis_names) if groups else ()
        slot = out.setdefault("+".join(axes) or "replica",
                              {"calls": 0, "bytes": 0, "ops": {}})
        slot["calls"] += 1
        slot["bytes"] += nbytes
        slot["ops"][op] = slot["ops"].get(op, 0) + nbytes
    return out


# ---- what each instruction is -------------------------------------------
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z0-9\-]*)\(")
_HEADER_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS_RE = re.compile(
    r"\b(?:calls|to_apply|body|condition|branch_computations)="
    r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')

PALLAS_TARGET = "tpu_custom_call"
_MATMUL_OPCODES = ("convolution", "dot")
_WRAPPER_OPCODES = ("while", "conditional", "call", "async-start",
                    "async-done")


def instruction_table(hlo_text):
    """``{instruction name: {"category", "opcode", "op_name"}}`` over every
    computation of the module; a category is one of ``pallas_kernel``,
    ``matmul_conv``, ``collective``, ``fusion``, ``copy``, ``control``,
    ``other_op`` (a name the table lacks is the caller's
    ``unattributed``). A fusion (or a call) is ``matmul_conv``
    when the computation it calls holds a convolution or a dot: on the
    TPU a matrix multiplication is a convolution inside an output
    fusion, and its fused epilogue is part of the same device event."""
    instrs = {}
    holds = {}  # computation -> opcodes it holds, callees still unresolved
    calls = {}
    current = None
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            h = _HEADER_RE.match(line)
            if h:
                current = h.group(1)
            continue
        name, rest = m.group(1), m.group(2)
        op = _OPCODE_RE.search(rest)
        if op is None:
            continue
        opcode = op.group(1)
        called = []
        c = _CALLS_RE.search(rest)
        if c:
            called = [x.strip().lstrip("%") for x in c.group(1).split(",")]
        target = _TARGET_RE.search(rest)
        op_name = _OPNAME_RE.search(rest)
        instrs[name] = {"opcode": opcode, "calls": called,
                        "target": target.group(1) if target else None,
                        "op_name": op_name.group(1) if op_name else ""}
        holds.setdefault(current, set()).add(opcode)
        calls.setdefault(current, set()).update(called)

    def reaches_matmul(comp, seen):
        if comp in seen:
            return False
        seen.add(comp)
        if holds.get(comp, set()) & set(_MATMUL_OPCODES):
            return True
        return any(reaches_matmul(c, seen) for c in calls.get(comp, ()))

    table = {}
    for name, info in instrs.items():
        opcode = info["opcode"]
        base = re.sub(r"-(start|done|update)$", "", opcode)
        if opcode == "custom-call" and info["target"] == PALLAS_TARGET:
            category = "pallas_kernel"
        elif base in COLLECTIVE_OPS:
            category = "collective"
        elif opcode in _MATMUL_OPCODES:
            category = "matmul_conv"
        elif opcode in _WRAPPER_OPCODES:
            category = "control"
        elif opcode == "fusion":
            category = ("matmul_conv" if any(
                reaches_matmul(c, set()) for c in info["calls"])
                else "fusion")
        elif base == "copy":
            category = "copy"
        else:
            category = "other_op"
        table[name] = {"category": category, "opcode": opcode,
                       "op_name": info["op_name"]}
    return table
