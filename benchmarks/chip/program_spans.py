"""The program's own spans (``repro.core.tracing``) inside the benchmark's.

The program records each checkpoint phase as a span on
``time.perf_counter``, the clock of the benchmark's ``Recorder``, so a
program span lies inside the benchmark span that called into it. Each
reader of a program span takes the spans inside every benchmark span of one
name (``migrate``, ``restore``, ``save_call``: ``bench.*`` in the trace)
and averages over them, as the host-clock metric it splits (``migrate_s``,
``restore_s``, ``save_call_s``) does.

A program without the tracing module records no spans: every reader then
returns None.
"""
from __future__ import annotations


def program_spans(rec: dict):
    """The program's finished spans as dicts (name, id, parent, t0, t1,
    attrs, counters): ``rec["program_spans"]`` where given, else the
    process's ``repro.core.tracing.spans()``; None without the module."""
    if "program_spans" in rec:
        return rec["program_spans"]
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing.spans()


def _inside(outer: dict, s: dict) -> bool:
    return outer["t0"] <= s["t0"] and s["t1"] is not None \
        and s["t1"] <= outer["t1"]


def per_bench_span(rec: dict, bench: str, name: str, value):
    """Mean over the benchmark spans ``bench`` of the sum of
    ``value(span)`` over the program spans ``name`` inside each; None where
    no such program span lies inside any of them."""
    spans = program_spans(rec)
    outers = [s for s in rec["spans"] if s["name"] == bench]
    if not spans or not outers:
        return None
    sums, found = [], False
    for b in outers:
        inner = [s for s in spans if s["name"] == name and _inside(b, s)]
        found = found or bool(inner)
        sums.append(sum(value(s) for s in inner))
    return sum(sums) / len(sums) if found else None


def seconds_in(rec: dict, bench: str, name: str):
    """Mean seconds per ``bench`` span spent in program spans ``name``."""
    return per_bench_span(rec, bench, name, lambda s: s["t1"] - s["t0"])


def counter_seconds_in(rec: dict, bench: str, name: str, counter: str):
    """Mean thread-seconds per ``bench`` span of ``counter`` on the
    program spans ``name`` inside it."""
    return per_bench_span(
        rec, bench, name,
        lambda s: s["counters"].get(counter, {}).get("seconds", 0.0))


def started_by(rec: dict, bench: str, name: str):
    """Mean wall seconds of the program spans ``name`` submitted inside
    each ``bench`` span and run later (an async job: ``attrs["queued_s"]``
    is its wait from submit to start)."""
    spans = program_spans(rec)
    outers = [s for s in rec["spans"] if s["name"] == bench]
    if not spans or not outers:
        return None
    walls = []
    for b in outers:
        walls += [s["t1"] - s["t0"] for s in spans
                  if s["name"] == name and s["t1"] is not None
                  and s["attrs"].get("queued_s") is not None
                  and b["t0"] <= s["t0"] - s["attrs"]["queued_s"] <= b["t1"]]
    return sum(walls) / len(outers) if walls else None
