"""From a profiler trace (``.xplane.pb``) to busy and idle seconds, per-program
device time, and the breakdown a result line carries.

    python3 benchmark/trace_reduce.py <file.xplane.pb>      # prints one JSON object

Runs as a short-lived child of the harness with ``JAX_PLATFORMS=cpu``: reading
a trace needs JAX's reader, not a chip, and the harness itself never imports
JAX.  Checked on the recorded trace in ``benchmark/tests/data``.
"""

from __future__ import annotations

import bisect
import collections
import json
import re
import sys

SPAN_PREFIX = "bm/"  # launcher.PREFIX: the benchmark's host spans
TOP = 10


def _union(intervals: "list[tuple[int, int]]") -> "list[tuple[int, int]]":
    out: "list[list[int]]" = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _gaps(busy: "list[tuple[int, int]]", lo: int, hi: int) -> "list[tuple[int, int]]":
    out, at = [], lo
    for b_lo, b_hi in busy:
        if b_lo > at:
            out.append((at, min(b_lo, hi)))
        at = max(at, b_hi)
    if at < hi:
        out.append((at, hi))
    return out


def attribute_idle(gaps: "list[tuple[int, int]]",
                   spans: "list[tuple[int, int, str]]") -> "dict[str, float]":
    """Share every idle nanosecond among the host spans open at that moment
    (equally, when several threads are inside one); ``_no_span_`` where the
    host was in none."""
    points = []
    for lo, hi in gaps:
        points.append((lo, 0, "+gap"))
        points.append((hi, 0, "-gap"))
    for lo, hi, name in spans:
        points.append((lo, 1, "+" + name))
        points.append((hi, -1, "-" + name))
    points.sort(key=lambda p: (p[0], p[1]))
    open_spans: "collections.Counter[str]" = collections.Counter()
    in_gap, last = False, 0
    out: "dict[str, float]" = collections.defaultdict(float)
    for at, _, what in points:
        if in_gap and at > last:
            live = [n for n, c in open_spans.items() if c > 0]
            for n in live or ["_no_span_"]:
                out[n] += (at - last) / len(live or [1])
        last = at
        sign, name = what[0], what[1:]
        if name == "gap":
            in_gap = sign == "+"
        else:
            open_spans[name] += 1 if sign == "+" else -1
    return {k: v / 1e9 for k, v in out.items()}


def program_name(module_event: str) -> str:
    """``jit_encode_words_fused1(123456789)`` -> ``jit_encode_words_fused1``"""
    return re.sub(r"\(\d+\)$", "", module_event)


def op_name(event: str) -> str:
    """An XLA op's event is its whole HLO line; its name is what stands before `` = ``."""
    return event.split(" = ", 1)[0].lstrip("%")[:120]


def reduce_planes(planes: "list[dict]") -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}]"""
    device = [p for p in planes if re.match(r"^/device:TPU:\d+$", p["name"])]
    everything = [(s, s + d) for p in planes for ln in p["lines"] for _, s, d in ln["events"]]
    if not device or not everything:
        return {}
    t_lo = min(s for s, _ in everything)
    t_hi = max(e for _, e in everything)
    spans = [(s, s + d, n[len(SPAN_PREFIX):]) for p in planes if p not in device
             for ln in p["lines"] for n, s, d in ln["events"] if n.startswith(SPAN_PREFIX)]
    busy_s, programs, calls, ops = [], collections.Counter(), collections.Counter(), \
        collections.Counter()
    idle_by_span: "dict[str, float]" = {}
    for rank, p in enumerate(device):
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        op_events = lines.get("XLA Ops") or [e for ev in lines.values() for e in ev]
        mod_events = sorted(lines.get("XLA Modules", []), key=lambda e: e[1])
        busy = _union([(s, s + d) for _, s, d in op_events])
        busy_s.append(sum(hi - lo for lo, hi in busy) / 1e9)
        starts = [s for _, s, _ in mod_events]
        for name, s, d in mod_events:
            programs[program_name(name)] += d / 1e9
            calls[program_name(name)] += 1
        for name, s, d in op_events:
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < mod_events[i][1] + mod_events[i][2]
            ops[(program_name(mod_events[i][0]) + "/" if inside else "") + name] += d / 1e9
        if rank == 0:
            idle_by_span = attribute_idle(_gaps(busy, t_lo, t_hi), spans)
    n = len(device)
    return {
        "window_s": (t_hi - t_lo) / 1e9,
        "busy_s": sum(busy_s) / n,
        "busy_s_by_device": busy_s,
        "program_s": {k: v / n for k, v in programs.items()},
        "program_calls": dict(calls),
        "device_ops": [[k, v / n] for k, v in ops.most_common(TOP)],
        "idle_gaps": sorted(([k, v] for k, v in idle_by_span.items()),
                            key=lambda kv: -kv[1])[:TOP],
        "host_spans": len(spans),
    }


def read_planes(path: str) -> "list[dict]":
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(op_name(e.name), int(e.start_ns), int(e.duration_ns))
                                   for e in ln.events]} for ln in p.lines]}
            for p in data.planes]


if __name__ == "__main__":
    print(json.dumps(reduce_planes(read_planes(sys.argv[1]))))
