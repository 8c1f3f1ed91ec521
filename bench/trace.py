"""Profiler traces: capture one window, and reduce it to the numbers the
per-layer metrics read.

An event is a dict {"plane", "line", "name", "ts", "dur"} with times in
nanoseconds on the profile's one clock.
The reduction:

- device ops are the events on the "XLA Ops" line of each "/device:"
  plane;
- `busy_s` is the union of a plane's op intervals, averaged over planes;
- an op's name is its HLO instruction's ("%move_rows.1 = f32[...] ..." is
  `move_rows`): a Pallas kernel's instruction carries the kernel's name;
- a kernel's time is the sum of the durations of its ops, and its call
  count the number of them;
- `device_ops` lists the names whose top-level ops took most time (an op
  inside a while loop's body counts in the loop, not again on its own);
- `idle_gaps` lists the longest gaps between device ops inside the window,
  each named by the innermost host event that spans it.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile

OPS_LINE = "XLA Ops"
TOP = 10


def load_events(path: str) -> list[dict]:
    """Every event of an `.xplane.pb` file."""
    from jax.profiler import ProfileData
    return [{"plane": plane.name, "line": line.name, "name": ev.name,
             "ts": float(ev.start_ns), "dur": float(ev.duration_ns)}
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events]


def device_ops(events: list[dict]) -> dict[str, list[dict]]:
    """Device op events by plane."""
    out: dict[str, list[dict]] = {}
    for e in events:
        if e["plane"].startswith("/device:") and e["line"] == OPS_LINE:
            out.setdefault(e["plane"], []).append(e)
    return out


def merged(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(ops: list[dict]) -> float:
    return sum(t - s for s, t in merged((e["ts"], e["ts"] + e["dur"])
                                        for e in ops))


def op_name(name: str) -> str:
    """'%fusion.12 = f32[8]{0} fusion(...)' -> 'fusion'."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def kernel_ops(ops: list[dict], kernel: str) -> list[dict]:
    """The ops that run Pallas kernel `kernel` (named after it)."""
    return [e for e in ops if op_name(e["name"]) == kernel]


def top_level(ops: list[dict]) -> list[dict]:
    """The ops of one plane that no other op's interval contains."""
    out, end = [], float("-inf")
    for e in sorted(ops, key=lambda e: (e["ts"], -e["dur"])):
        if e["ts"] + e["dur"] <= end:
            continue
        out.append(e)
        end = max(end, e["ts"] + e["dur"])
    return out


def top_ops(ops: list[dict], n: int = TOP) -> list[list]:
    tot: dict[str, float] = {}
    for e in ops:
        k = op_name(e["name"])
        tot[k] = tot.get(k, 0.0) + e["dur"] * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops: list[dict], host: list[dict], n: int = TOP) -> list[list]:
    """The n longest gaps between device ops, each named by the shortest
    host event that spans it (or the one that overlaps it most)."""
    busy = merged((e["ts"], e["ts"] + e["dur"]) for e in ops)
    gaps = sorted(((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
                   for i in range(len(busy) - 1)), reverse=True)[:n]
    out = []
    for length, s, t in gaps:
        spans = [h for h in host if h["ts"] <= s and h["ts"] + h["dur"] >= t]
        if spans:
            name = min(spans, key=lambda h: h["dur"])["name"]
        else:
            best = max(host, default=None, key=lambda h: min(
                t, h["ts"] + h["dur"]) - max(s, h["ts"]))
            name = best["name"] if best is not None else "no host event"
        out.append([name, length * 1e-9])
    return out


def reduce(events: list[dict], kernels=()) -> dict:
    """busy_s (mean over chips), per-kernel (seconds, calls) and the
    breakdown of one traced window."""
    by_plane = device_ops(events)
    if not by_plane:
        raise RuntimeError("the trace holds no device op")
    host = [e for e in events if e["plane"].startswith("/host:")]
    ops = [e for plane in by_plane.values() for e in plane]
    n = len(by_plane)
    first = next(iter(by_plane.values()))
    return {
        "busy_s": sum(busy_ns(p) for p in by_plane.values()) * 1e-9 / n,
        "kernels": {k: [sum(e["dur"] for e in kernel_ops(ops, k)) * 1e-9 / n,
                        len(kernel_ops(ops, k)) // n] for k in kernels},
        "breakdown": {"device_ops": top_ops(
            [e for p in by_plane.values() for e in top_level(p)]),
                      "idle_gaps": idle_gaps(first, host)},
    }


class Capture:
    """What `capture` hands its caller: `reduce(kernels)` once it closed."""

    def __init__(self, path: str | None):
        self.path = path

    def reduce(self, kernels=()) -> dict:
        try:
            events = load_events(self.path)
        finally:
            os.remove(self.path)
        return reduce(events, kernels)


@contextlib.contextmanager
def capture(enabled: bool, tmpdir: str):
    """Trace the body with the JAX profiler when `enabled`; the trace is
    read into memory by `Capture.reduce` and its files removed."""
    if not enabled:
        yield Capture(None)
        return
    import jax
    out = tempfile.mkdtemp(prefix="trace-", dir=tmpdir)
    cap = Capture(None)
    jax.profiler.start_trace(out)
    try:
        yield cap
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                          recursive=True)
        if found:
            keep = os.path.join(tmpdir, os.path.basename(out) + ".xplane.pb")
            shutil.move(found[0], keep)
            cap.path = keep
        shutil.rmtree(out, ignore_errors=True)
