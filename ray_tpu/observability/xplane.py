"""Dependency-free XPlane (``*.xplane.pb``) trace reader.

``jax.profiler`` captures land as TensorBoard ``XSpace`` protobufs
(``plugins/profile/<run>/<host>.xplane.pb``). Reading them normally
requires tensorflow + tensorboard_plugin_profile — neither ships in
this image, and the bench harness must be able to turn a device
capture into a *slice breakdown* (which ops ate the step, matmul vs
not) with zero extra deps. So this module walks the protobuf wire
format directly against the stable XPlane schema (tsl/profiler
``xplane.proto`` field numbers, unchanged since 2020):

    XSpace.planes=1
    XPlane.name=2 .lines=3 .event_metadata=4 .stat_metadata=5
    XLine.name=2 .events=4 .display_name=11
    XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3 .stats=4
           .num_occurrences=5
    XEventMetadata.id=1 .name=2 .metadata=3 .display_name=4
    XStat.metadata_id=1 (+ oneof value fields 2-7)
    XStatMetadata.id=1 .name=2

``summarize_trace`` reads a capture the way the benchmark does
(``benchmark/benchlib/trace.py`` and ``program_trace.py``; the rules
are restated here because the program does not import the benchmark):
an operation's self time, classed by its parsed HLO opcode and fusion
kind, and grouped by the program scope in its ``op_name``. On this
jaxlib a device event is named by its whole HLO instruction and carries
no ``op_name``; the capture's ``/host:metadata`` plane holds each
module's ``HloProto``, which does (HloProto.hlo_module=1,
HloModuleProto.computations=3, HloComputationProto.instructions=2,
HloInstructionProto.name=1 .metadata=7 .id=35 .operand_ids=36,
OpMetadata.op_name=2).

Consumers: ``observability.profiler.device_trace_summary`` (the remote
``profile_device`` post-processing, ``ray_tpu profile --device``), and
the tier-1 tests (the CPU backend also emits xplane files, so the
parser is testable without a chip).
"""

from __future__ import annotations

import glob
import os
import re
import struct

__all__ = [
    "parse_xspace", "trace_files", "summarize_trace", "parse_hlo",
    "classify", "scope_path", "SCOPES",
]

# The program scopes every operation of a train step falls under
# (docs/observability.md); flax module names lie beneath them.
SCOPES = ("embed", "blocks", "loss", "optimizer")

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")
HLO_NAME = re.compile(r"^%?([\w.\-]+)")
HLO_OPCODE = re.compile(r"^\s*([\w\-]+)\(")
HLO_KIND = re.compile(r"\bkind=(k\w+)")


# ---------------------------------------------------------------------------
# protobuf wire-format walker


def _read_varint(buf: bytes, i: int) -> tuple[int, int]:
    result = 0
    shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7
        if shift > 70:
            raise ValueError("malformed varint")


def _fields(buf: bytes, start: int = 0, end: int | None = None):
    """Yield (field_number, wire_type, value) triples.

    value: int for varint(0)/fixed64(1)/fixed32(5), bytes-slice
    (memoryview-free copy) for length-delimited(2).
    """
    i = start
    end = len(buf) if end is None else end
    while i < end:
        tag, i = _read_varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            val, i = _read_varint(buf, i)
        elif wire == 1:
            val = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wire == 2:
            ln, i = _read_varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wire == 5:
            val = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, val


def _utf8(b: bytes) -> str:
    return b.decode("utf-8", errors="replace")


def _parse_event(buf: bytes) -> dict:
    ev = {"metadata_id": 0, "offset_ps": 0, "duration_ps": 0,
          "stats": []}
    for f, _, v in _fields(buf):
        if f == 1:
            ev["metadata_id"] = v
        elif f == 2:
            ev["offset_ps"] = v
        elif f == 3:
            ev["duration_ps"] = v
        elif f == 4:
            ev["stats"].append(_parse_stat(v))
        elif f == 5:
            ev["num_occurrences"] = v
    return ev


def _parse_stat(buf: bytes) -> dict:
    st: dict = {"metadata_id": 0, "value": None}
    for f, wire, v in _fields(buf):
        if f == 1:
            st["metadata_id"] = v
        elif f == 2:
            st["value"] = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif f in (3, 4, 7):
            st["value"] = v
        elif f == 5:
            st["value"] = _utf8(v)
        elif f == 6:
            st["value"] = v  # raw bytes
    return st


def _parse_line(buf: bytes) -> dict:
    line = {"name": "", "display_name": "", "timestamp_ns": 0,
            "events": []}
    for f, _, v in _fields(buf):
        if f == 2:
            line["name"] = _utf8(v)
        elif f == 3:
            line["timestamp_ns"] = v
        elif f == 11:
            line["display_name"] = _utf8(v)
        elif f == 4:
            line["events"].append(_parse_event(v))
    return line


def _parse_metadata_entry(buf: bytes) -> tuple[int, dict]:
    """One map<int64, XEventMetadata|XStatMetadata> entry."""
    key = 0
    meta = {"name": "", "display_name": "", "stats": []}
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            for mf, _, mv in _fields(v):
                if mf == 1:
                    key = key or mv
                elif mf == 2:
                    meta["name"] = _utf8(mv)
                elif mf == 4:
                    meta["display_name"] = _utf8(mv)
                elif mf == 5:
                    meta["stats"].append(_parse_stat(mv))
    return key, meta


def _parse_plane(buf: bytes) -> dict:
    plane = {"name": "", "lines": [], "event_metadata": {},
             "stat_metadata": {}}
    for f, _, v in _fields(buf):
        if f == 2:
            plane["name"] = _utf8(v)
        elif f == 3:
            plane["lines"].append(_parse_line(v))
        elif f == 4:
            k, meta = _parse_metadata_entry(v)
            plane["event_metadata"][k] = meta
        elif f == 5:
            k, meta = _parse_metadata_entry(v)
            plane["stat_metadata"][k] = meta
    return plane


def parse_xspace(path: str) -> dict:
    """Parse one ``.xplane.pb`` file -> {"planes": [...]}."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for f_no, _, v in _fields(buf):
        if f_no == 1:
            planes.append(_parse_plane(v))
    return {"planes": planes}


# ---------------------------------------------------------------------------
# trace summary


def trace_files(logdir: str) -> list[str]:
    """All xplane protobufs under a ``jax.profiler`` logdir."""
    pats = (os.path.join(logdir, "**", "*.xplane.pb"),
            os.path.join(logdir, "*.xplane.pb"))
    out: list[str] = []
    for p in pats:
        out.extend(glob.glob(p, recursive=True))
    return sorted(set(out))


def parse_hlo(text: str) -> tuple[str, str, str]:
    """(name, opcode, fusion kind) of an event named by its HLO
    instruction, ``%name = <shape> opcode(operands), attributes``. An
    event with a plain name gives (name, "", "")."""
    name = HLO_NAME.match(text)
    name = name.group(1) if name else text
    _, eq, rest = text.partition(" = ")
    if not eq:
        return name, "", ""
    if rest.startswith("("):            # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    opcode = HLO_OPCODE.match(rest)
    kind = HLO_KIND.search(rest)
    return (name, opcode.group(1) if opcode else "",
            kind.group(1) if kind else "")


def classify(name: str, opcode: str = "", kind: str = "") -> str:
    """``collective``, ``kernel`` (custom calls: the Pallas kernels),
    ``mxu`` (convolutions, dots and the fusions rooted in them: the TPU
    compiler's ``kOutput`` fusions) or ``other``. An event with a
    plain name (the CPU backend's) is classed by that name."""
    op = opcode or name
    if COLLECTIVE.match(op):
        return "collective"
    if op.startswith("custom-call"):
        return "kernel"
    if (op.startswith(("convolution", "dot")) or kind == "kOutput"
            or "convolution" in name):
        return "mxu"
    return "other"


def scope_path(op_name: str) -> str:
    """The program scope of an operation and the modules beneath it,
    from its ``op_name``: ``jit(step)/transpose(jvp(GPT2))/blocks/h_3/
    attn/dot_general`` gives ``blocks/h_*/attn`` (transforms unwrapped,
    the primitive dropped, digits starred, two levels kept). An
    operation under none of ``SCOPES`` gives ``unscoped``."""
    parts = []
    for part in op_name.split("/"):
        if not part.startswith(("jit(", "pjit(")):
            part = part.rsplit("(", 1)[-1].rstrip(")")
        parts.append(part)
    for i, part in enumerate(parts):
        if part in SCOPES:
            below = [re.sub(r"\d+", "*", x) for x in parts[i + 1:-1][:2]]
            return "/".join([part, *below])
    return "unscoped"


def _op_names(planes: list[dict]) -> dict[str, dict[str, str]]:
    """module (as the ``XLA Modules`` line names it) -> instruction
    name -> ``op_name``, from the HloProtos of ``/host:metadata``."""
    out: dict[str, dict[str, str]] = {}
    for plane in planes:
        if plane["name"] != "/host:metadata":
            continue
        for meta in plane["event_metadata"].values():
            names = out.setdefault(meta["name"], {})
            for st in meta["stats"]:
                if not isinstance(st["value"], bytes):
                    continue
                for f, _, module in _fields(st["value"]):
                    if f != 1:
                        continue
                    for mf, _, comp in _fields(module):
                        if mf == 3:
                            names.update(_computation_op_names(comp))
    return out


def _computation_op_names(comp: bytes) -> dict[str, str]:
    """instruction name -> ``op_name`` within one computation. An
    instruction the compiler added (a copy into faster memory, a
    convert) carries none: it serves whatever reads its result, so it
    takes the ``op_name`` of the nearest user that has one of its own
    (breadth first through users that have none)."""
    rows = []
    for cf, _, inst in _fields(comp):
        if cf != 2:
            continue
        row = {"name": "", "op_name": "", "id": 0, "operands": [],
               "inherited": False}
        for f, wire, v in _fields(inst):
            if f == 1 and wire == 2:
                row["name"] = _utf8(v)
            elif f == 7 and wire == 2:
                for mf, mwire, mv in _fields(v):
                    if mf == 2 and mwire == 2:
                        row["op_name"] = _utf8(mv)
            elif f == 35:
                row["id"] = v
            elif f == 36 and wire == 2:     # repeated int64, packed
                i = 0
                while i < len(v):
                    operand, i = _read_varint(v, i)
                    row["operands"].append(operand)
            elif f == 36:
                row["operands"].append(v)
        rows.append(row)
    users: dict[int, list[dict]] = {}
    for r in rows:
        for operand in r["operands"]:
            users.setdefault(operand, []).append(r)
    for r in rows:
        if r["op_name"]:
            continue
        seen, frontier = {r["id"]}, [r]
        while frontier and not r["inherited"]:
            reached = [u for x in frontier for u in users.get(x["id"], ())
                       if u["id"] not in seen]
            seen.update(u["id"] for u in reached)
            own = [u for u in reached
                   if u["op_name"] and not u["inherited"]]
            if own:
                r["op_name"], r["inherited"] = own[0]["op_name"], True
            frontier = reached
    return {r["name"]: r["op_name"] for r in rows
            if r["name"] and r["op_name"]}


def _pick_plane(planes: list[dict]) -> dict | None:
    """Device plane preference: TPU > GPU > any /device: > busiest."""
    def n_events(p):
        return sum(len(ln["events"]) for ln in p["lines"])
    for marker in ("/device:tpu", "/device:gpu", "/device:"):
        cand = [p for p in planes
                if marker in p["name"].lower() and n_events(p)]
        if cand:
            return max(cand, key=n_events)
    with_events = [p for p in planes if n_events(p)]
    return max(with_events, key=n_events) if with_events else None


def _pick_lines(plane: dict) -> list[dict]:
    """Per-op lines only: 'XLA Ops' when present (the 'XLA Modules' /
    'Steps' lines span whole programs and would double-count)."""
    ops = [ln for ln in plane["lines"]
           if (ln["name"] or ln["display_name"]).lower() == "xla ops"]
    if ops:
        return ops
    lines = [ln for ln in plane["lines"] if ln["events"]]
    if not lines:
        return []
    return [max(lines, key=lambda ln: len(ln["events"]))]


def _self_times(events: list[dict]) -> None:
    """Adds ``self_ps``: an event's duration less the events nested
    directly inside it (the body of a ``while``), so a loop is not
    counted twice."""
    events.sort(key=lambda e: (e["start"], -e["end"]))
    stack: list[dict] = []
    for e in events:
        e["self_ps"] = e["end"] - e["start"]
        while stack and stack[-1]["end"] <= e["start"]:
            stack.pop()
        if stack and e["end"] <= stack[-1]["end"]:
            stack[-1]["self_ps"] -= e["end"] - e["start"]
        stack.append(e)


def _line_events(plane: dict, line: dict) -> list[dict]:
    base = line["timestamp_ns"] * 1000
    out = []
    for ev in line["events"]:
        meta = plane["event_metadata"].get(ev["metadata_id"], {})
        text = (meta.get("name") or meta.get("display_name")
                or f"#{ev['metadata_id']}")
        start = base + ev["offset_ps"]
        out.append({"text": text, "start": start,
                    "end": start + ev["duration_ps"]})
    return out


def _module_at(modules: list[dict], t: int) -> str:
    for m in modules:
        if m["start"] <= t < m["end"]:
            return m["text"]
    return ""


def summarize_trace(logdir: str, top_k: int = 5,
                    steps: int = 1) -> dict:
    """Aggregate a capture into the slice breakdown an operator
    reads, by the rules of ``benchmark/``: self time of the
    ``XLA Ops`` line of the busiest device plane, classed by HLO
    opcode and fusion kind, and grouped by program scope.

    Returns ``{"plane", "files", "total_ms", "ms_per_step",
    "class_ms": {"mxu", "kernel", "collective", "other"},
    "matmul_ms", "non_matmul_ms", "matmul_share",
    "scope_ms": {"blocks/h_*/attn": ..., "loss": ..., "unscoped": ...},
    "top_matmul": [{"name", "ms", "share"}...], "top_non_matmul"}``.
    ``*_ms`` are totals over the capture except ``class_ms`` and
    ``scope_ms`` and the rows' ``ms``, which are per step (``steps``
    optimizer steps ran in the profiled window). ``scope_ms`` is empty
    where the capture carries no HLO metadata (the CPU backend); where
    it does and no operation lies under a scope, ``scope_note`` says
    what that means. Raises ValueError when the logdir holds no usable
    capture.
    """
    files = trace_files(logdir)
    if not files:
        raise ValueError(f"no xplane captures under {logdir}")
    per_op: dict[str, list] = {}   # label -> [self_ps, class]
    classes = {"mxu": 0, "kernel": 0, "collective": 0, "other": 0}
    scopes: dict[str, int] = {}
    plane_name = ""
    for path in files:
        space = parse_xspace(path)
        plane = _pick_plane(space["planes"])
        if plane is None:
            continue
        plane_name = plane_name or plane["name"]
        op_names = _op_names(space["planes"])
        modules = [e for ln in plane["lines"] if ln["name"] == "XLA Modules"
                   for e in _line_events(plane, ln)]
        for line in _pick_lines(plane):
            events = _line_events(plane, line)
            _self_times(events)
            for e in events:
                name, opcode, kind = parse_hlo(e["text"])
                cls = classify(name, opcode, kind)
                classes[cls] += e["self_ps"]
                cell = per_op.setdefault(name, [0, cls])
                cell[0] += e["self_ps"]
                if op_names:
                    names = op_names.get(
                        _module_at(modules, e["start"]), {})
                    scope = scope_path(names.get(name, ""))
                    scopes[scope] = scopes.get(scope, 0) + e["self_ps"]
    if not per_op:
        raise ValueError(
            f"captures under {logdir} carry no per-op events")
    total_ps = sum(classes.values())
    n = max(1, steps)

    def per_step_ms(ps: int) -> float:
        return round(ps / 1e9 / n, 3)

    def rows(matmul: bool):
        items = sorted(
            ((name, v[0]) for name, v in per_op.items()
             if (v[1] == "mxu") == matmul),
            key=lambda kv: kv[1], reverse=True)[:top_k]
        return [{"name": name[:120], "ms": per_step_ms(ps),
                 "share": round(ps / max(1, total_ps), 4)}
                for name, ps in items]

    note = {}
    if set(scopes) == {"unscoped"}:
        note["scope_note"] = (
            "no operation lies under embed, blocks, loss or optimizer: "
            "the executable was compiled by a program without these "
            "scopes, or loaded from a compile cache that one filled (jax "
            "finds an entry without looking at op_name). Profile with "
            "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=1 or an empty "
            "cache directory.")
    return {
        **note,
        "plane": plane_name,
        "files": len(files),
        "total_ms": round(total_ps / 1e9, 3),
        "ms_per_step": per_step_ms(total_ps),
        "class_ms": {k: per_step_ms(v) for k, v in classes.items()},
        "matmul_ms": round(classes["mxu"] / 1e9, 3),
        "non_matmul_ms": round((total_ps - classes["mxu"]) / 1e9, 3),
        "matmul_share": round(classes["mxu"] / max(1, total_ps), 4),
        "scope_ms": {k: per_step_ms(v) for k, v in
                     sorted(scopes.items(), key=lambda kv: -kv[1])},
        "top_non_matmul": rows(False),
        "top_matmul": rows(True),
    }
