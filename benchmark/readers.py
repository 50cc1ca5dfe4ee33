"""The arithmetic from a run's records, counters and trace to its metrics.

One file under ``benchmark/metrics/`` per metric calls one function here; the
functions are kept together so that the rate and tail arithmetic is read, and
tested, in one place.  A reader that finds nothing to read returns None and
the harness leaves the metric out of the line.

A ``run`` carries: ``t0``/``t1`` (window, CLOCK_MONOTONIC), ``records`` (every
request of the run phase, generator.Record), ``ks_open``/``ks_close`` (the
server's kernel-stats at the window's ends), ``ks_trace_open``/``ks_trace_close``
and ``trace`` (traced runs), ``marks`` (per worker: name -> (monotonic, cpu
seconds)), ``config``, ``traffic``, ``sample``.
"""

from __future__ import annotations

import math
import statistics

import roofline

MIB = 1 << 20
MISSED_MS = 300_000.0  # a request that failed or was shed missed every limit


# -- the window ----------------------------------------------------------------


def completed(run) -> list:
    """Requests answered rightly inside the window."""
    return [r for r in run.records
            if run.t0 <= r.end <= run.t1 and not r.failed and not r.wrong]


def due_in_window(run, kind: "str | None" = None) -> list:
    return [r for r in run.records
            if run.t0 <= r.due <= run.t1 and (kind is None or r.kind == kind)]


def ended_in_window(run, kind: "str | None" = None) -> list:
    return [r for r in run.records
            if run.t0 <= r.end <= run.t1 and (kind is None or r.kind == kind)]


def window(run) -> float:
    return run.t1 - run.t0


def payload_rate(run) -> float:
    """Object bytes PUT and GET completed in the window over the window: all
    the work over all the time."""
    return sum(r.nbytes for r in completed(run)) / MIB / window(run)


def op_rate(run) -> float:
    return len(completed(run)) / window(run)


def percentile(values: "list[float]", p: float) -> "float | None":
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(p / 100.0 * len(s)) - 1))]


def _latency_ms(r, since_due: bool) -> float:
    if r.failed or r.wrong:
        return MISSED_MS
    return (r.end - (r.due if since_due else r.start)) * 1e3


def open_tail(run, kind: str, p: float) -> "float | None":
    """Open loop: every request of the kind that was due in the window, timed
    from when it was due; a failure or a 503 misses it."""
    if run.traffic["loop"] != "open":
        return None
    return percentile([_latency_ms(r, True) for r in due_in_window(run, kind)], p)


def closed_tail(run, kind: str, p: float) -> "float | None":
    if run.traffic["loop"] != "closed":
        return None
    return percentile([_latency_ms(r, False) for r in ended_in_window(run, kind)], p)


def gen_late(run) -> "float | None":
    if run.traffic["loop"] != "open":
        return None
    return percentile([(r.start - r.due) * 1e3 for r in due_in_window(run)], 95)


def backlog_end(run) -> "float | None":
    """Requests due before the window closed and unanswered when it did."""
    if run.traffic["loop"] != "open":
        return None
    return float(sum(1 for r in run.records if r.due <= run.t1 < r.end))


def shed_share(run) -> "float | None":
    seen = ended_in_window(run)
    if not seen:
        return None
    return 100.0 * sum(1 for r in seen if r.status == 503) / len(seen)


def gen_busy(run) -> "float | None":
    """Cores used by the busiest generator process over the window."""
    shares = []
    for marks in run.marks:
        if "open" in marks and "close" in marks:
            (ta, ca), (tb, cb) = marks["open"], marks["close"]
            shares.append((cb - ca) / (tb - ta))
    return max(shares) if shares else None


# -- the server's counters (kernel-stats), as deltas over the window ------------


def _rows(ks: dict, table: str, **where) -> "list[dict]":
    return [row for row in ks.get(table, [])
            if all(row.get(k) == v for k, v in where.items())]


def _delta(run, pick, trace: bool = False) -> float:
    a = run.ks_trace_open if trace else run.ks_open
    b = run.ks_trace_close if trace else run.ks_close
    if a is None or b is None:
        return 0.0
    return pick(b) - pick(a)


def _stage_ms_per_stream(run, stage: str) -> "float | None":
    secs = _delta(run, lambda ks: sum(r["seconds"] for r in _rows(ks, "stages", stage=stage)))
    n = _delta(run, lambda ks: sum(r["streams"] for r in _rows(ks, "stages", stage=stage)))
    return 1e3 * secs / n if n else None


def stream_assemble(run) -> "float | None":
    return _stage_ms_per_stream(run, "assemble")


def stream_disk(run) -> "float | None":
    return _stage_ms_per_stream(run, "disk")


def batch_fill(run) -> "float | None":
    flushes = _delta(run, lambda ks: ks["batch"]["flushes"])
    return _delta(run, lambda ks: ks["batch"]["jobs"]) / flushes if flushes else None


def batch_wait(run) -> "float | None":
    jobs = _delta(run, lambda ks: ks["batch"]["jobs"])
    return 1e3 * _delta(run, lambda ks: ks["batch"]["wait_seconds"]) / jobs if jobs else None


def seam_time(run) -> "float | None":
    calls = _delta(run, lambda ks: sum(r["calls"] for r in ks["ops"]))
    secs = _delta(run, lambda ks: sum(r["seconds"] for r in ks["ops"]))
    return 1e3 * secs / calls if calls else None


def bus_ratio(run) -> "float | None":
    """Bytes over the host-device bus, both ways, per payload byte completed."""
    moved = _delta(run, lambda ks: sum(r["bytes"] for r in ks["h2d"] + ks["d2h"]))
    payload = sum(r.nbytes for r in completed(run))
    return moved / payload if payload and moved else None


def iopool_busy(run) -> "float | None":
    queues = run.ks_close["iopool"]["queues"] if run.ks_close else []
    if not queues:
        return None
    busy = _delta(run, lambda ks: sum(q["busy_seconds"] for q in ks["iopool"]["queues"]))
    return 100.0 * busy / (window(run) * len(queues))


def spanned_share(run) -> "float | None":
    span = _delta(run, lambda ks: ks["placement"]["span"])
    route = _delta(run, lambda ks: ks["placement"]["route"])
    return 100.0 * span / (span + route) if span + route else None


def compiles(ks: dict) -> int:
    cc = ks["device"]["compile_cache"]
    return int(cc["hits"]) + int(cc["misses"])


def window_compiles(run) -> "float | None":
    if run.ks_open is None or run.ks_close is None:
        return None
    return float(compiles(run.ks_close) - compiles(run.ks_open))


def hbm_peak(run) -> "float | None":
    peak = run.device.get("memory_peak_bytes")
    return peak / MIB if peak else None


def drive_write_ratio(run) -> "float | None":
    """Bytes the sampled objects hold on the drives per byte of user data."""
    s = run.sample or {}
    return s["stored_bytes"] / s["user_bytes"] if s.get("user_bytes") else None


# -- the trace -------------------------------------------------------------------


def device_idle(run) -> "float | None":
    t = run.trace
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def device_idle_worst(run) -> "float | None":
    t = run.trace
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - min(t["busy_s_by_device"]) / t["window_s"])


def kernel_roofline(run, program: str, op: str, cost) -> "float | None":
    """The program's device seconds in the trace against the least the chip
    could take for the useful bytes and operations the seam counted meanwhile."""
    t = run.trace
    if not t:
        return None
    secs = sum(v for k, v in t.get("program_s", {}).items() if k.startswith(program))
    payload = _delta(run, lambda ks: sum(r["bytes"] for r in _rows(ks, "ops", op=op)), trace=True)
    if not secs or not payload:
        return None
    e = run.config["erasure"]
    nbytes, nops = cost(payload, e["data"], e["parity"], len(run.traffic.get("lost_drives", [])))
    return 100.0 * roofline.least_seconds(run.device["kind"], nbytes, nops) / secs


def median_ms(run, kind: str) -> "float | None":
    v = [_latency_ms(r, False) for r in ended_in_window(run, kind)]
    return statistics.median(v) if v else None


def latency_summary(run) -> dict:
    """Per kind: count and p50/p90/p95 in ms (from the due time in an open loop)."""
    open_loop = run.traffic["loop"] == "open"
    out = {}
    for kind in sorted({r.kind for r in run.records}):
        rs = due_in_window(run, kind) if open_loop else ended_in_window(run, kind)
        v = [_latency_ms(r, open_loop) for r in rs]
        if v:
            out[kind] = {"n": len(v), **{f"p{p}": percentile(v, p) for p in (50, 90, 95)}}
    return out
