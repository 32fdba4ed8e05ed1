"""From a profiler trace to the program's own spans and what they cover.

The program marks the stages of the routed hop as profiler annotations
(`repro.spans` in the program): ``encode`` with its children
``encode/tokenize``, ``encode/dispatch`` and ``encode/fetch``, and
``route`` with ``route/dispatch`` and ``route/fetch``.  They land on the
calling thread's host line (``python3`` for the main thread on a TPU
host), on the same clock as the device's operations.  `reduce` works on
the event list of `trace.events` alone, so a small recorded list checks
it without a chip; a trace without such spans (a program that has none)
reduces to empty calls and all idle time ``outside``.

* ``calls``: for each span name, one entry per call: its seconds, its self
  seconds (less its direct children) and the seconds of its direct
  children summed by name.  A child is a span nested inside another on
  the same host line.
* ``programs``: device seconds of each program (line ``XLA Modules`` of
  the first device) by its name before the hash, so that
  ``jit_query_encoder(<hash>)`` is read as ``jit_query_encoder`` whatever
  the hash.
* ``idle``: the first device's idle seconds between the trace's first and
  last event, each put down to the innermost span open on the host at that
  moment, and ``outside`` for the rest.

From a reduction, `metrics` gives six per-layer readings (ms a wave),
`stages` splits a wave by span and `idle_summary` gives the ``idle by
span`` line.  The benchmark's result line does not carry them:
`bench/spans_report.py` reads them from the events that ``run.py
--trace-out`` writes.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from . import stats
from . import trace as trace_mod

#: the program's top-level spans; a child is named ``<layer>/<stage>``
LAYERS = ("encode", "route")
OUTSIDE = "outside"


def is_span(name: str) -> bool:
    return name.split("/", 1)[0] in LAYERS


def program_name(name: str) -> str:
    """A program's name without its hash: ``jit_run(123)`` -> ``jit_run``."""
    return name.split("(", 1)[0]


def nest(evs: List[list]) -> List[dict]:
    """The program spans of every host line, each with its ``depth`` and
    its direct children (``kids``)."""
    by_line: Dict[Tuple[str, str], List[list]] = {}
    for p, line, name, s, d in evs:
        if not p.startswith(trace_mod.DEVICE_PREFIX) and is_span(name):
            by_line.setdefault((p, line), []).append([s, s + d, name])
    out = []
    for rows in by_line.values():
        stack: List[dict] = []
        for s, e, name in sorted(rows, key=lambda r: (r[0], -r[1])):
            # a child lies within its parent; a sibling that starts as the
            # last one ends is no child of it, whatever the rounding
            while stack and (stack[-1]["end"] <= s or stack[-1]["end"] < e):
                stack.pop()
            node = {"name": name, "start": s, "end": e, "kids": [],
                    "depth": len(stack)}
            if stack:
                stack[-1]["kids"].append(node)
            stack.append(node)
            out.append(node)
    return out


def labelled(nodes: List[dict]) -> List[list]:
    """Disjoint sorted ``[start, end, name]`` segments: where any span is
    open, the innermost (deepest) one."""
    marks = sorted([(n["start"], 1, n["depth"], n["name"]) for n in nodes]
                   + [(n["end"], -1, n["depth"], n["name"]) for n in nodes])
    active: Dict[Tuple[int, str], int] = {}
    segs: List[list] = []
    prev = None
    for t, step, depth, name in marks:
        if active and prev is not None and t > prev:
            label = max(active)[1]
            if segs and segs[-1][1] == prev and segs[-1][2] == label:
                segs[-1][1] = t
            else:
                segs.append([prev, t, label])
        key = (depth, name)
        active[key] = active.get(key, 0) + step
        if not active[key]:
            del active[key]
        prev = t
    return segs


def idle_by_span(evs: List[list], nodes: List[dict]) -> Dict[str, float]:
    """Idle seconds of the first device put down to the innermost open
    span, and ``outside`` for the idle time no span covers."""
    planes = trace_mod.device_planes(evs)
    if not planes:
        return {}
    lo = min(e[3] for e in evs)
    hi = max(e[3] + e[4] for e in evs)
    idle, t = [], lo
    for s, e in trace_mod.busy_ns(evs, planes[0]):
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if hi > t:
        idle.append((t, hi))
    out = {OUTSIDE: 0.0}
    segs = labelled(nodes)
    j = 0
    for s, e in idle:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            ov = min(e, segs[k][1]) - max(s, segs[k][0])
            if ov > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + ov / 1e9
                covered += ov
            k += 1
        out[OUTSIDE] += (e - s - covered) / 1e9
    return out


def reduce(evs: List[list]) -> Dict:
    """``calls``, ``programs`` and ``idle`` (module docstring)."""
    nodes = nest(evs)
    calls: Dict[str, List[dict]] = {}
    for n in nodes:
        kids: Dict[str, float] = {}
        for k in n["kids"]:
            kids[k["name"]] = kids.get(k["name"], 0.0) + (
                k["end"] - k["start"]) / 1e9
        dur = (n["end"] - n["start"]) / 1e9
        calls.setdefault(n["name"], []).append(
            {"s": dur, "self_s": dur - sum(kids.values()), "children": kids})
    planes = trace_mod.device_planes(evs)
    programs: Dict[str, float] = {}
    for p, line, name, _, d in evs:
        if planes and p == planes[0] and line == trace_mod.PROGRAMS_LINE:
            key = program_name(name)
            programs[key] = programs.get(key, 0.0) + d / 1e9
    return {"calls": calls, "programs": programs,
            "idle": idle_by_span(evs, nodes)}


def idle_summary(sp: Dict) -> Dict:
    """The ``idle by span`` line of a reduction: the idle seconds, the
    share of them inside a program span, and each span's (and
    ``outside``'s) seconds and share, largest first."""
    idle = sp["idle"]
    total = sum(idle.values())
    pct = (lambda s: 100.0 * s / total) if total else (lambda s: 0.0)
    return {"idle_s": total,
            "in_spans_pct": pct(total - idle.get(OUTSIDE, 0.0)),
            "by_span": {k: [s, pct(s)] for k, s in sorted(
                idle.items(), key=lambda kv: -kv[1])}}


# --- the per-layer readings, in milliseconds a wave ----------------------

def median_ms(values: List[float]) -> Optional[float]:
    """Median (nearest rank) of ``values`` seconds, in milliseconds."""
    m = stats.percentile(values, 50)
    return None if m is None else 1000.0 * m


def _device_per_call_ms(sp: Dict, program: str, calls: List[dict]
                        ) -> Optional[float]:
    s = sp["programs"].get(program) if calls else None
    return None if s is None else 1000.0 * s / len(calls)


def metrics(sp: Dict) -> Dict[str, float]:
    """The six readings of a reduction, leaving out those it holds nothing
    for (a trace without spans reads none):

    * ``tokenize_ms``: median of ``encode/tokenize``;
    * ``encode_fetch_ms``: median over ``encode`` of its ``encode/fetch``
      children's sum;
    * ``encoder_device_ms``: device time of ``jit_query_encoder`` over the
      ``encode`` spans;
    * ``route_host_ms``: median of ``route`` less its ``route/fetch``
      children (preparation, uploads, dispatch);
    * ``route_fetch_ms``: median over ``route`` of its ``route/fetch``
      children's sum;
    * ``route_device_ms``: device time of ``jit__serve_fused_jit`` over
      the ``route`` spans.
    """
    calls = sp["calls"]
    enc, route = calls.get("encode", []), calls.get("route", [])
    out = {
        "tokenize_ms": median_ms(
            [c["s"] for c in calls.get("encode/tokenize", [])]),
        "encode_fetch_ms": median_ms(
            [c["children"].get("encode/fetch", 0.0) for c in enc]),
        "encoder_device_ms": _device_per_call_ms(
            sp, "jit_query_encoder", enc),
        "route_host_ms": median_ms(
            [c["s"] - c["children"].get("route/fetch", 0.0) for c in route]),
        "route_fetch_ms": median_ms(
            [c["children"].get("route/fetch", 0.0) for c in route]),
        "route_device_ms": _device_per_call_ms(
            sp, "jit__serve_fused_jit", route),
    }
    return {k: v for k, v in out.items() if v is not None}


def stages(sp: Dict) -> Dict[str, float]:
    """A wave split by stage (median over the calls of each layer's span):
    each direct child summed per call, the self time, and the whole."""
    out = {}
    for layer in LAYERS:
        calls = sp["calls"].get(layer, [])
        if not calls:
            continue
        kids = sorted({k for c in calls for k in c["children"]})
        for k in kids:
            out[k] = median_ms([c["children"].get(k, 0.0) for c in calls])
        out[f"{layer} self"] = median_ms([c["self_s"] for c in calls])
        out[layer] = median_ms([c["s"] for c in calls])
    return out
