"""The layer reduction of a traced train window: every device op put down
to the program's layer scope, and every idle gap to the launcher loop's
host span that overlaps it most.

It reads what `trace.load_events` reads, the op names of the compiled
step (`hlo_op_names(compiled.as_text())`, taken after the window), and the
program's scope names (`repro.tracing.SCOPES`, passed in):

- an op's instruction is the name its event carries ("%fusion.115 = ..."
  is `fusion.115`); its scope is the innermost name of the scopes among
  the path components of the instruction's `op_name` metadata
  (`jvp(x)` and, in the backward, `transpose(jvp(x))` are `x`; `jit(x)`
  names a function, not a scope). A fusion carries its root op's;
- only ops inside the step module's intervals (the events of the device's
  "XLA Modules" line named after the HLO module) are looked up; every
  other op is unscoped;
- at each instant of busy time the innermost running op, the one that
  started last, holds the device; an unscoped op inside a scoped one (a
  loop's body op) takes the enclosing op's scope. A layer's time is the
  time its ops hold the device: nested ops count once, and layer times
  plus the unscoped time are the busy time;
- `device_ops` is `trace.reduce`'s list with the top-level ops of each
  scope apart, named `<scope>/<op kind>` (unscoped ops keep their kind);
- an idle gap between device ops is named by the `train.*` host span that
  overlaps it most, else as `trace.idle_gaps` names it.

Times are seconds over the whole window, averaged over device planes.
"""
from __future__ import annotations

import bisect
import re

from bench import trace

MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "train."

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=%]+) = .*?op_name="([^"]*)"',
                    re.M)
_WRAPPED = re.compile(r"([\w-]+)\((.*)\)")


def hlo_op_names(hlo_text: str) -> tuple[str, dict[str, str]]:
    """(module name, {instruction: op_name}) of an HLO module's text."""
    head = re.match(r"\s*HloModule\s+([^\s,]+)", hlo_text)
    if head is None:
        raise ValueError("not an HLO module's text")
    return head.group(1), dict(_INSTR.findall(hlo_text))


def scope_of(op_name: str, scopes) -> tuple[str | None, bool]:
    """(innermost scope in `op_name`, whether it is a backward op)."""
    found, backward = None, False
    for part in op_name.split("/"):
        inner, bwd = part, False
        while (m := _WRAPPED.fullmatch(inner)) and m.group(1) != "jit":
            bwd = bwd or m.group(1) == "transpose"
            inner = m.group(2)
        if inner in scopes:
            found, backward = inner, bwd
    return found, backward


def instruction(name: str) -> str:
    """'%fusion.12 = f32[8]{0} fusion(...)' -> 'fusion.12'."""
    return name.split(" = ", 1)[0].lstrip("%")


def _inside(ops: list[dict], spans: list[tuple[float, float]]) -> list[bool]:
    """Whether each op starts inside one of the sorted, disjoint spans."""
    starts = [a for a, _ in spans]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e["ts"]) - 1
        out.append(i >= 0 and e["ts"] < spans[i][1])
    return out


def hold_times(ops: list[dict], labels: list) -> dict:
    """Nanoseconds each label holds the device: at each instant the
    running op that started last holds it, and an op labelled None that
    lies inside the op holding the device takes that op's label."""
    out: dict = {}
    stack: list[list] = []          # [end, label] of the ops running
    now = 0.0                       # device time is credited up to here

    def run_to(t: float) -> None:
        nonlocal now
        while stack:
            end, label = stack[-1]
            upto = min(end, t)
            if upto > now:
                out[label] = out.get(label, 0.0) + upto - now
                now = upto
            if end > t:
                break
            stack.pop()
        now = max(now, t)

    order = sorted(range(len(ops)), key=lambda i: (ops[i]["ts"],
                                                   -ops[i]["dur"]))
    for i in order:
        s, t = ops[i]["ts"], ops[i]["ts"] + ops[i]["dur"]
        if not stack:
            now = s
        run_to(s)
        label = labels[i]
        if label is None and stack and stack[-1][0] >= t:
            label = stack[-1][1]
        stack.append([t, label])
    run_to(float("inf"))
    return out


def _gaps(ops: list[dict], host: list[dict], n: int):
    """The n longest idle gaps, each named by the `train.*` span that
    overlaps it most (else by `trace.idle_gaps`), and the idle seconds of
    all gaps by the span each goes to ("no train span" where none)."""
    spans = [h for h in host if h["name"].startswith(SPAN_PREFIX)]
    busy = trace.merged((e["ts"], e["ts"] + e["dur"]) for e in ops)
    gaps = sorted(((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
                   for i in range(len(busy) - 1)), reverse=True)

    def span_of(s, t):
        best, most = None, 0.0
        for h in spans:
            over = min(t, h["ts"] + h["dur"]) - max(s, h["ts"])
            if over > most:
                best, most = h["name"], over
        return best

    named = [span_of(s, t) for _, s, t in gaps]
    top = [[name or old, length] for name, (old, length) in zip(
        named, trace.idle_gaps(ops, host, n))]
    by_span: dict[str, float] = {}
    for name, (length, _, _) in zip(named, gaps):
        key = name or "no train span"
        by_span[key] = by_span.get(key, 0.0) + length * 1e-9
    return top, by_span


def reduce(events: list[dict], module: str, op_names: dict[str, str],
           scopes, n: int = trace.TOP) -> dict:
    """The layer breakdown of one traced window (see the module's
    docstring). `module` and `op_names` are `hlo_op_names` of the step.

    Returns {"steps": step-module runs on the first device, "layer_s":
    {scope: s}, "backward_s": {scope: s of its backward ops},
    "unscoped_s", "busy_s", "device_ops", "idle_gaps", "gap_s": {span:
    idle s}}, with the scopes that held the device at all."""
    by_plane = trace.device_ops(events)
    if not by_plane:
        raise RuntimeError("the trace holds no device op")
    scope_map = {k: sc for k, v in op_names.items()
                 if (sc := scope_of(v, scopes))[0] is not None}
    host = [e for e in events if e["plane"].startswith("/host:")]
    layer_s: dict[str, float] = {}
    backward_s: dict[str, float] = {}
    unscoped = busy = 0.0
    steps, top = [], []
    for plane, ops in by_plane.items():
        runs = [e for e in events if e["plane"] == plane
                and e["line"] == MODULES_LINE
                and e["name"].startswith(module + "(")]
        steps.append(len(runs))
        inside = _inside(ops, trace.merged(
            (e["ts"], e["ts"] + e["dur"]) for e in runs))
        labels = [scope_map.get(instruction(e["name"])) if ok else None
                  for e, ok in zip(ops, inside)]
        for label, ns in hold_times(ops, labels).items():
            busy += ns
            if label is None:
                unscoped += ns
                continue
            layer_s[label[0]] = layer_s.get(label[0], 0.0) + ns
            if label[1]:
                backward_s[label[0]] = backward_s.get(label[0], 0.0) + ns
        top += trace.top_level([{**e, "label": lab}
                                for e, lab in zip(ops, labels)])

    renamed = [{"name": f"{e['label'][0]}/{trace.op_name(e['name'])}"
                if e["label"] else e["name"], "dur": e["dur"]} for e in top]
    planes = len(by_plane)
    idle, gap_s = _gaps(next(iter(by_plane.values())), host, n)
    return {"steps": steps[0],
            "layer_s": {k: v * 1e-9 / planes for k, v in layer_s.items()},
            "backward_s": {k: v * 1e-9 / planes
                           for k, v in backward_s.items()},
            "unscoped_s": unscoped * 1e-9 / planes,
            "busy_s": busy * 1e-9 / planes,
            "device_ops": trace.top_ops(renamed, n),
            "idle_gaps": idle, "gap_s": gap_s}


def layer_ms(rec: dict, *scopes: str) -> float | None:
    """Device ms per step that `scopes` held in a record carrying
    `reduce`'s result under "layers" (None where none of them ran)."""
    lay = rec.get("layers")
    if not lay or not lay["steps"] or not any(
            s in lay["layer_s"] for s in scopes):
        return None
    return 1e3 * sum(lay["layer_s"].get(s, 0.0)
                     for s in scopes) / lay["steps"]
