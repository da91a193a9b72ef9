"""A run's set-up as one timeline, cut where the program says.

``setup_s`` runs from ``run.py``'s first line to the stamp that opens
the window. The program's train-path spans (``ray_tpu.util.tracing``;
docs/observability.md) are on the same ``time.monotonic()``, the
driver's and the worker's alike, so the interval is cut at two
instants the program tells, the start of the newest ``train.fit`` span
and the session's first ``train.report()`` (``train.worker.loop``'s
start plus its ``first_report_s``), into parts that sum to it:

    setup_s = before_fit_s                   entry.before_fit_s
            + named_s + unnamed_s            fit's start to the first report
            + warmup_s                       step.warmup_s

``named_s`` is what the union of the program's spans covers between
``train.fit``'s start and the first report, whichever process recorded
them, overlaps counted once, the three containers left out
(``train.fit``, ``train.fit.poll``, ``train.worker.loop``: each is open
the whole time). ``unnamed_s`` (``fit.setup_unnamed_s``) is the rest:
code of the user's loop under no span of the program.

The functions work on records ``{"name", "start", "end", "process",
"attributes"}`` made from the tracer's spans (the readers under
``layer_metrics/``) or from a ``fit_trace.json``
(``tools/setup_table.py``). Every reader returns None where the program
records no such span, as one from before these spans does.
"""

from __future__ import annotations

from benchlib import program_trace, trace

CONTAINERS = ("train.fit", "train.fit.poll", "train.worker.loop")


def records(spans) -> list[dict]:
    """From ``tracing.Span`` objects."""
    return [{"name": s.name, "start": s.mono_start, "end": s.mono_end,
             "process": s.process, "attributes": s.attributes}
            for s in spans]


def records_from_chrome(events: list[dict]) -> list[dict]:
    """From the events of a ``fit_trace.json``
    (``tracing.chrome_events``: microseconds on the monotonic clock)."""
    return [{"name": e["name"], "start": e["ts"] / 1e6,
             "end": (e["ts"] + e["dur"]) / 1e6, "process": e["pid"],
             "attributes": e["args"]} for e in events]


def label(record: dict) -> str:
    """A span's name; a compile's with its kind."""
    kind = record["attributes"].get("kind")
    return f"{record['name']}:{kind}" if kind else record["name"]


def fit_start(recs: list[dict]) -> float | None:
    starts = [r["start"] for r in recs if r["name"] == "train.fit"]
    return max(starts, default=None)


def first_report(recs: list[dict]) -> float | None:
    """When the fit's first ``train.report()`` was made: the earliest
    over its workers' loops."""
    told = [r["start"] + r["attributes"]["first_report_s"] for r in recs
            if r["name"] == "train.worker.loop"
            and "first_report_s" in r["attributes"]]
    return min(told, default=None)


def span_s(recs: list[dict], name: str, first: bool = False) -> float | None:
    """Seconds in one span of a name, of the newest gang's (those that
    began since the newest ``train.fit.gang_start`` did: a restart
    makes every worker's anew): the longest, since workers run side by
    side and the fit waits for the last; the earliest with ``first``."""
    since = max((r["start"] for r in recs
                 if r["name"] == "train.fit.gang_start"), default=0.0)
    found = [r for r in recs if r["name"] == name and r["start"] >= since]
    if not found:
        return None
    if first:
        found = [min(found, key=lambda r: r["start"])]
    return max(r["end"] - r["start"] for r in found)


def _clipped(recs: list[dict], a: float, b: float) -> list[dict]:
    """The spans that count as named, cut to [a, b]."""
    out = []
    for r in recs:
        lo, hi = max(r["start"], a), min(r["end"], b)
        if r["name"] not in CONTAINERS and hi > lo:
            out.append({**r, "start": lo, "end": hi})
    return out


def _named(recs: list[dict], a: float, b: float) -> list[tuple[float, float]]:
    """What the named spans cover of [a, b], overlaps merged."""
    return trace.merge([(r["start"], r["end"]) for r in _clipped(recs, a, b)])


def cut(recs: list[dict], t_start: float, t_open: float) -> dict | None:
    """The parts of ``t_open - t_start``; None without a ``train.fit``
    span. Without a first report only ``before_fit_s``."""
    t_fit = fit_start(recs)
    if t_fit is None:
        return None
    parts = {"setup_s": t_open - t_start, "before_fit_s": t_fit - t_start}
    t_report = first_report(recs)
    if t_report is None:
        return parts
    named_s = sum(b - a for a, b in _named(recs, t_fit, t_report))
    parts.update(named_s=named_s,
                 unnamed_s=(t_report - t_fit) - named_s,
                 warmup_s=t_open - t_report,
                 t_fit=t_fit, t_first_report=t_report)
    return parts


def named_by_label(recs: list[dict], a: float, b: float) -> dict[str, float]:
    """``named_s`` split by span: each instant of [a, b] under a named
    span goes to the one that began last (the innermost; a compile
    inside ``train.input.first_batch`` is the compile's)."""
    spans = _clipped(recs, a, b)
    edges = sorted({t for r in spans for t in (r["start"], r["end"])})
    out: dict[str, float] = {}
    for lo, hi in zip(edges, edges[1:]):
        over = [r for r in spans if r["start"] <= lo and r["end"] >= hi]
        if over:
            inner = max(over, key=lambda r: (r["start"], -r["end"]))
            out[label(inner)] = out.get(label(inner), 0.0) + (hi - lo)
    return out


def gaps(recs: list[dict], a: float, b: float) -> list[tuple[float, float]]:
    """The stretches of [a, b] under no named span: what ``unnamed_s``
    is the sum of."""
    return trace.subtract([(a, b)], _named(recs, a, b))


# -- for the readers: the spans of this process's newest fit --------------

def _fit_records() -> list[dict] | None:
    spans = program_trace.fit_spans()
    return None if spans is None else records(spans)


def part(run, key: str) -> float | None:
    """One part of ``cut`` for a finished :class:`report.Run`."""
    recs = _fit_records()
    if recs is None:
        return None
    parts = cut(recs, run.driver["t_start"],
                run.worker["stamps"][run.worker["open_i"]])
    return None if parts is None else parts.get(key)


def fit_span_s(name: str, first: bool = False) -> float | None:
    recs = _fit_records()
    return None if recs is None else span_s(recs, name, first)
