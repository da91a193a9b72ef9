"""From a ``jax.profiler`` trace (``*.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData`` (nothing but JAX). The traced
window is the host event ``bench.window`` that ``loop.py`` opens at one
stamp and closes at a later one; device events are clipped to it. All
seconds are averaged over the device planes, so on four chips they are
per chip.

- busy: the union of the intervals in which an operation runs on the
  device's ``XLA Ops`` line; idle is the rest of the window.
- self time: an operation's duration minus what the operations nested
  inside it (the body of a ``while``) cover, so a loop is not counted
  twice.
- classes. On this jaxlib an event of the ``XLA Ops`` line is named by
  its whole HLO instruction (``%fusion.3 = f32[..] fusion(..),
  kind=kOutput, calls=..``), so the class comes from the parsed opcode
  and fusion kind: ``kernel`` (``custom-call``: the Pallas kernels),
  ``collective``, ``mxu`` (``convolution``, ``dot``, and fusions of
  ``kind=kOutput`` or with "convolution" in their name: the TPU
  compiler's output fusions are rooted in a convolution), ``other``.
- collective time is the union of the collectives' intervals, an
  asynchronous pair counted from the start of its ``-start`` to the end
  of its ``-done``; exposed is the part of that during which no other
  operation ran on the same device.
- idle gaps are named by the ``bench.*`` host span that covers most of
  each; gaps under 20 us are summed under one name.
"""

from __future__ import annotations

import glob
import os
import re

SMALL_GAP_NS = 20_000
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")
HLO_NAME = re.compile(r"^%?([\w.\-]+)")
HLO_OPCODE = re.compile(r"^\s*([\w\-]+)\(")
HLO_KIND = re.compile(r"\bkind=(k\w+)")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def newest_trace_file(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def parse_hlo(text: str) -> tuple[str, str, str]:
    """(name, opcode, fusion kind) of an event named by its HLO
    instruction, ``%name = <shape> opcode(operands), attributes``. An
    event with a plain name gives (name, "", "")."""
    name = HLO_NAME.match(text)
    name = name.group(1) if name else text
    head, eq, rest = text.partition(" = ")
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


def classify(name: str, opcode: str, kind: str = "") -> str:
    op = opcode or name
    if COLLECTIVE.match(op):
        return "collective"
    if op.startswith("custom-call"):
        return "kernel"
    if (op.startswith(("convolution", "dot")) or kind == "kOutput"
            or "convolution" in name):
        return "mxu"
    return "other"


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def subtract(intervals, cover) -> list[tuple[float, float]]:
    """The parts of merged ``intervals`` that merged ``cover`` leaves."""
    out = []
    for a, b in intervals:
        at = a
        for c, d in cover:
            if d <= at:
                continue
            if c >= b:
                break
            if c > at:
                out.append((at, c))
            at = max(at, d)
        if at < b:
            out.append((at, b))
    return out


def self_times(events: list[dict]) -> None:
    """Adds ``self_ns`` and ``leaf`` to events of one line: duration
    minus the events nested directly inside."""
    events.sort(key=lambda e: (e["start"], -e["end"]))
    stack: list[dict] = []
    for e in events:
        e["self_ns"] = e["end"] - e["start"]
        e["leaf"] = True
        while stack and stack[-1]["end"] <= e["start"]:
            stack.pop()
        if stack and e["end"] <= stack[-1]["end"]:
            stack[-1]["self_ns"] -= e["end"] - e["start"]
            stack[-1]["leaf"] = False
        stack.append(e)


def _events(line, w0: float, w1: float) -> list[dict]:
    out = []
    for ev in line.events:
        a, b = max(ev.start_ns, w0), min(ev.end_ns, w1)
        if b <= a:
            continue
        name, opcode, kind = parse_hlo(ev.name)
        out.append({"name": name, "opcode": opcode, "kind": kind,
                    "start": a, "end": b})
    return out


def _host_spans(profile) -> tuple[tuple[float, float] | None, list[dict]]:
    """(the ``bench.window`` interval, the other ``bench.*`` spans)."""
    window, spans = None, []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.window":
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith("bench."):
                    spans.append({"name": ev.name, "start": ev.start_ns,
                                  "end": ev.end_ns})
    return window, spans


def _name_gap(a: float, b: float, spans: list[dict]) -> str:
    if b - a < SMALL_GAP_NS:
        return "gaps_under_20_us"
    best, most = "no_bench_span", 0.0
    for s in spans:
        over = min(b, s["end"]) - max(a, s["start"])
        if over > most:
            best, most = s["name"], over
    return best


def _async_pairs(colls: list[dict]) -> list[tuple[float, float]]:
    """Intervals of the collectives: a ``-start`` runs to the end of
    the next ``-done`` of the same operation."""
    out, open_at = [], {}
    for e in sorted(colls, key=lambda e: e["start"]):
        op = e["opcode"] or re.sub(r"\.\d+$", "", e["name"])
        base = re.sub(r"-(start|done)$", "", op)
        if op.endswith("-start"):
            open_at.setdefault(base, []).append(e["start"])
        elif op.endswith("-done") and open_at.get(base):
            out.append((open_at[base].pop(0), e["end"]))
        else:
            out.append((e["start"], e["end"]))
    return out


def reduce_profile(profile, steps: int) -> dict:
    """The numbers of one traced window; ``steps`` is how many
    optimizer steps it held."""
    window, spans = _host_spans(profile)
    planes = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    if not planes:
        raise ValueError("the trace holds no /device:TPU:<n> plane: "
                         f"{[p.name for p in profile.planes]}")
    n = len(planes)
    busy_ns = coll_ns = exposed_ns = 0.0
    classes = {"kernel": 0.0, "collective": 0.0, "mxu": 0.0, "other": 0.0}
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for plane in planes:
        lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        if not lines:
            raise ValueError(f"{plane.name} has no 'XLA Ops' line: "
                             f"{[ln.name for ln in plane.lines]}")
        if window is None:      # no host span: the extent of the ops
            starts = [ev.start_ns for ev in lines[0].events]
            ends = [ev.end_ns for ev in lines[0].events]
            window = (min(starts), max(ends))
        w0, w1 = window
        events = _events(lines[0], w0, w1)
        self_times(events)
        busy = merge([(e["start"], e["end"]) for e in events])
        busy_ns += sum(b - a for a, b in busy)
        for a, b in subtract([(w0, w1)], busy):
            label = _name_gap(a, b, spans)
            gaps[label] = gaps.get(label, 0.0) + (b - a)
        colls = []
        compute = []
        for e in events:
            kind = classify(e["name"], e["opcode"], e["kind"])
            classes[kind] += e["self_ns"]
            what = " ".join(x for x in (e["opcode"], e["kind"]) if x)
            label = f"{e['name']} [{what}]" if what else e["name"]
            ops[label] = ops.get(label, 0.0) + e["self_ns"]
            if kind == "collective":
                colls.append(e)
            elif e["leaf"]:
                compute.append((e["start"], e["end"]))
        coll = merge(_async_pairs(colls))
        coll_ns += sum(b - a for a, b in coll)
        exposed_ns += sum(b - a for a, b in subtract(coll, merge(compute)))

    def top(d: dict) -> list[list]:
        return [[k[:100], v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "devices": n, "steps": steps,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "class_s": {k: v / n / 1e9 for k, v in classes.items()},
        "collective_s": coll_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
        "idle_by_span_s": {k: v / n / 1e9 for k, v in gaps.items()},
        "device_ops": top(ops), "idle_gaps": top(gaps),
    }


def reduce_trace(trace_dir: str, steps: int) -> dict | None:
    """Reduce the newest trace under ``trace_dir``; None if there is
    none."""
    path = newest_trace_file(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), steps)
