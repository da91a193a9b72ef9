"""What the program's own instrumentation says about a run.

``trace.py`` classes device time by HLO opcode and times the host from
outside (``bench.*``). This file reads what ``ray_tpu`` itself puts on
the train path (PERF.md section 3, docs/observability.md):

- the spans of the newest ``train.fit`` in this process, from
  ``ray_tpu.util.tracing.get_spans()``: fit's phases on the driver and
  in the worker and every compile, stamped with ``time.monotonic()``,
  the clock of ``run.py``'s ``T_START`` and of the loop's stamps;
- the run's profile, found from the root span's ``trial_dir`` (``run.py``
  puts the experiments two levels under the run's directory, beside
  ``trace/``), reduced once a run: device self time of the ``XLA Ops``
  line inside ``bench.window`` grouped by the program scope (``embed``,
  ``blocks``, ``loss``, ``optimizer``) in each operation's ``op_name``;
  the host's ``train.*`` annotations of every thread inside the
  window; idle gaps of 20 us and more named by the ``train.*`` span
  that covers most of each.

On this jaxlib a device event is named by its whole HLO instruction and
carries no ``op_name``. The profile's ``/host:metadata`` plane holds the
``HloProto`` of each module, which does; ``ProfileData`` does not show
event metadata, so those bytes are walked here (XSpace.planes=1;
XPlane.name=2 .event_metadata=4; XEventMetadata.name=2 .stats=5;
XStat.bytes_value=6; HloProto.hlo_module=1; HloModuleProto.computations=3;
HloComputationProto.instructions=2; HloInstructionProto.name=1
.metadata=7 .id=35 .operand_ids=36; OpMetadata.op_name=2). An
instruction the compiler added without an ``op_name`` (an asynchronous
copy into faster memory, a convert) is counted under the scope of the
nearest instruction that reads its result.

Every function returns None where there is nothing to read: no trace,
no ``train.fit`` span (a program from before these spans), no scope (an
executable from before them, also out of a compile cache).
"""

from __future__ import annotations

import functools
import os

from benchlib import trace

SCOPES = ("embed", "blocks", "loss", "optimizer")


# -- the spans of fit() -------------------------------------------------

def fit_spans() -> list | None:
    """The spans of the newest ``train.fit`` this process made, root
    first; None where the program records none."""
    from ray_tpu.util import tracing
    roots = [s for s in tracing.get_spans() if s.name == "train.fit"]
    if not roots:
        return None
    root = max(roots, key=lambda s: s.mono_end)
    rest = [s for s in tracing.get_spans(root.trace_id) if s is not root]
    return [root, *rest]


def phase_s(name: str) -> float | None:
    """Seconds in the spans of one name (a phase repeats when a gang
    restarts)."""
    spans = [s for s in fit_spans() or () if s.name == name]
    if not spans:
        return None
    return sum(s.mono_end - s.mono_start for s in spans)


def compile_s(kinds: tuple[str, ...], before: float) -> float | None:
    """Seconds covered by the ``train.compile`` spans of these kinds
    that ended before ``before`` (``time.monotonic()``), overlaps
    counted once."""
    spans = fit_spans()
    if spans is None:
        return None
    covered = trace.merge([
        (s.mono_start, s.mono_end) for s in spans
        if s.name == "train.compile" and s.attributes.get("kind") in kinds
        and s.mono_end <= before])
    return sum(b - a for a, b in covered)


# -- the profile, by program scope and train.* span -----------------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return n, i


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message; the
    value of a fixed-width field is not read."""
    i, end = 0, len(buf)
    while i < end:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, wire, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, wire, buf[i:i + n]
            i += n
        else:
            i += 8 if wire == 1 else 4
            yield field, wire, None


def _sub(buf: bytes, *path: int):
    """Every length-delimited value reached by ``path`` of field
    numbers."""
    if not path:
        yield buf
        return
    for f, wire, v in _fields(buf):
        if f == path[0] and wire == 2:
            yield from _sub(v, *path[1:])


def _instruction(inst: bytes) -> dict:
    """name, op_name, id and operand ids of one HloInstructionProto."""
    row = {"name": "", "op_name": "", "id": 0, "operands": [],
           "inherited": False}
    for f, wire, v in _fields(inst):
        if f == 1 and wire == 2:
            row["name"] = v.decode()
        elif f == 7 and wire == 2:
            row["op_name"] = next(_sub(v, 2), b"").decode()
        elif f == 35:
            row["id"] = v
        elif f == 36 and wire == 2:     # repeated int64, packed
            i = 0
            while i < len(v):
                operand, i = _varint(v, i)
                row["operands"].append(operand)
        elif f == 36:
            row["operands"].append(v)
    return row


def _inherit(rows: list[dict]) -> None:
    """An instruction the compiler added (a copy into faster memory, a
    convert, a bitcast) carries no ``op_name``. It serves whatever
    reads its result, so it takes the ``op_name`` of the nearest user
    that has one of its own (breadth first through users that have
    none), and is marked ``inherited``."""
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


def op_names(xspace: bytes) -> dict[str, dict[str, tuple[str, bool]]]:
    """module (as the ``XLA Modules`` line names it) -> instruction
    name -> (``op_name``, whether it was inherited from a user)."""
    out: dict[str, dict[str, tuple[str, bool]]] = {}
    for plane in _sub(xspace, 1):
        if next(_sub(plane, 2), b"") != b"/host:metadata":
            continue
        for meta in _sub(plane, 4, 2):
            module = next(_sub(meta, 2), b"").decode()
            names = out.setdefault(module, {})
            for computation in _sub(meta, 5, 6, 1, 3):
                rows = [_instruction(i) for i in _sub(computation, 2)]
                _inherit(rows)
                for r in rows:
                    if r["name"] and r["op_name"]:
                        names[r["name"]] = (r["op_name"], r["inherited"])
    return out


def scope_of(op_name: str) -> tuple[str, tuple[str, ...]]:
    """(scope, the names beneath it) from an ``op_name`` such as
    ``jit(step)/transpose(jvp(GPT2))/blocks/h_3/attn/dot_general``:
    transforms are unwrapped (``transpose(jvp(loss))`` is ``loss``).
    An operation under none of SCOPES gives ("unscoped", ())."""
    parts = []
    for part in op_name.split("/"):
        if not part.startswith(("jit(", "pjit(")):
            part = part.rsplit("(", 1)[-1].rstrip(")")
        parts.append(part)
    for i, part in enumerate(parts):
        if part in SCOPES:
            return part, tuple(parts[i + 1:])
    return "unscoped", ()


def _clipped(line, w0: float, w1: float) -> list[dict]:
    out = []
    for ev in line.events:
        a, b = max(ev.start_ns, w0), min(ev.end_ns, w1)
        if b > a:
            out.append({"text": ev.name, "start": a, "end": b})
    return out


def reduce_profile(profile, names: dict[str, dict[str, str]],
                   steps: int) -> dict | None:
    """Seconds per device (mean over the device planes) inside
    ``bench.window``; None without that span or a device plane."""
    window, annotated = None, []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:            # one line a thread
            for ev in line.events:
                if ev.name == "bench.window":
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith("train."):
                    annotated.append((ev.name, ev.start_ns, ev.end_ns))
    planes = [p for p in profile.planes if trace.DEVICE_PLANE.match(p.name)]
    if window is None or not planes:
        return None
    w0, w1 = window
    host: dict[str, float] = {}         # train.* seconds, every thread
    train_spans = []
    for name, start, end in annotated:
        a, b = max(start, w0), min(end, w1)
        if b > a:
            host[name] = host.get(name, 0.0) + (b - a) / 1e9
            train_spans.append({"name": name, "start": a, "end": b})
    n = len(planes)
    scope: dict[str, float] = {}
    inherited = 0.0     # of that, scoped through a user's op_name
    within: dict[str, float] = {}       # under blocks: attn, mlp
    idle: dict[str, float] = {}
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            return None
        modules = (_clipped(lines["XLA Modules"], w0, w1)
                   if "XLA Modules" in lines else [])
        events = _clipped(lines["XLA Ops"], w0, w1)
        trace.self_times(events)
        for e in events:
            module = next((m["text"] for m in modules
                           if m["start"] <= e["start"] < m["end"]), "")
            name = trace.parse_hlo(e["text"])[0]
            op_name, lent = names.get(module, {}).get(name, ("", False))
            top, below = scope_of(op_name)
            scope[top] = scope.get(top, 0.0) + e["self_ns"] / n / 1e9
            if lent and top != "unscoped":
                inherited += e["self_ns"] / n / 1e9
            if top == "blocks":
                for sub in ("attn", "mlp"):
                    if sub in below:
                        within[sub] = (within.get(sub, 0.0)
                                       + e["self_ns"] / n / 1e9)
        busy = trace.merge([(e["start"], e["end"]) for e in events])
        for a, b in trace.subtract([(w0, w1)], busy):
            if b - a < trace.SMALL_GAP_NS:
                continue
            best, most = "no_train_span", 0.0
            for s in train_spans:
                over = min(b, s["end"]) - max(a, s["start"])
                if over > most:
                    best, most = s["name"], over
            idle[best] = idle.get(best, 0.0) + (b - a) / n / 1e9
    return {"devices": n, "steps": steps, "window_s": (w1 - w0) / 1e9,
            "scope_s": scope, "inherited_s": inherited,
            "blocks_s": within, "host_s": host,
            "idle_by_span_s": idle}


@functools.lru_cache(maxsize=2)
def reduce_file(path: str, steps: int) -> dict | None:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return reduce_profile(ProfileData.from_serialized_xspace(raw),
                          op_names(raw), steps)


def of_run(run) -> dict | None:
    """The reduction of a traced run's profile (read once a run)."""
    spans = fit_spans()
    if run.trace is None or spans is None:
        return None
    trial_dir = spans[0].attributes.get("trial_dir")
    if not trial_dir:
        return None
    path = trace.newest_trace_file(os.path.join(
        os.path.dirname(os.path.dirname(trial_dir)), "trace"))
    return reduce_file(path, run.trace["steps"]) if path else None


def scope_ms_per_step(run, scope: str, within: str = "") -> float | None:
    """Device milliseconds a step under one scope (or, ``within``
    ``blocks``, under the modules named ``attn`` or ``mlp``); None where
    the program's step carries no such scope. Every step that
    ``train/step.py`` builds has ``optimizer``: an executable without it
    was compiled by a program from before the scopes, or loaded from a
    compile cache that one filled (jax finds an entry without looking at
    metadata, so a hit brings the ``op_name`` of whoever compiled it)."""
    got = of_run(run)
    if got is None or "optimizer" not in got["scope_s"]:
        return None         # a program without scopes: nothing to split
    seconds = (got["blocks_s"].get(within) if within
               else got["scope_s"].get(scope, 0.0 if scope == "unscoped"
                                       else None))
    return None if seconds is None else seconds / got["steps"] * 1e3


def host_ms_per_step(run, name: str) -> float | None:
    """Host milliseconds a step inside one ``train.*`` annotation,
    every thread, in the traced window."""
    got = of_run(run)
    if got is None or not got["host_s"]:
        return None
    return got["host_s"].get(name, 0.0) / got["steps"] * 1e3
