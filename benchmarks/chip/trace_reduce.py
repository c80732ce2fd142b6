"""Reduce a JAX profiler trace of the window to device busy time, idle
share, the top device operations, and idle gaps labelled by the host span
(the benchmark's ``bench.*`` TraceAnnotations) they fall in.

Two stages, so the arithmetic can be checked on a small recorded trace:
``load`` reads the ``.xplane.pb`` into plain lists of
``[name, start_ns, end_ns]``, ``reduce`` does the rest.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW = "bench.window"


def load(path: str) -> dict:
    """{"devices": {plane: [[op, t0, t1], ...]}, "host": [[span, t0, t1]]}
    with times in ns on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [[op_name(e.name), e.start_ns, e.end_ns]
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[plane.name] = ops
        else:
            host += [[e.name, e.start_ns, e.end_ns]
                     for line in plane.lines for e in line.events
                     if e.name.startswith(HOST_PREFIX)]
    return {"devices": devices, "host": host}


def op_name(hlo: str) -> str:
    """An XLA Ops event is named by its whole HLO instruction; keep the
    instruction's name (``%fusion.12``)."""
    return hlo.split(" = ", 1)[0]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _label(host, a, b):
    """The innermost host span holding the gap's midpoint."""
    mid = (a + b) / 2
    inside = [(e - s, n) for n, s, e in host
              if n != WINDOW and s <= mid <= e]
    return min(inside)[1][len(HOST_PREFIX):] if inside else "other"


def reduce(events: dict, top: int = 10) -> dict:
    """busy_s and window_s (seconds, busy averaged over the devices), the
    idle share, and the breakdown lists, each at most `top` long."""
    (w0, w1), = [(s, e) for n, s, e in events["host"] if n == WINDOW][:1]
    devices = [d for d in events["devices"].values() if d]
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy, ops, gaps = 0.0, {}, {}
    for d in devices:
        clipped = [[max(s, w0), min(e, w1), n] for n, s, e in d
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            ops[n] = ops.get(n, 0.0) + (e - s)
        u = _union([[s, e] for s, e, _ in clipped])
        busy += sum(e - s for s, e in u)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                lab = _label(events["host"], a, b)
                gaps[lab] = gaps.get(lab, 0.0) + (b - a)
    n = len(devices)
    busy_s, window_s = busy / n / 1e9, (w1 - w0) / 1e9

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps)}


def idle_percent(rec: dict):
    """Share of the traced window in which no operation ran on the device,
    averaged over the chips, in percent; None without a trace."""
    tr = rec["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def reduce_dir(trace_dir: str) -> dict:
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return reduce(load(path))
