"""The arithmetic of the metrics of the deployment that runs the program's
defaults (``ec8p4-12d-defaults``): hedged shard reads, reads of a healthy set
that decode by reconstruct, loss patterns and their matrices.

Every reader is a delta of ``kernel-stats`` between the snapshots the harness
already takes (``run.ks_open`` / ``run.ks_close``; the traced slice's for the
roofline share), of the counters the program keeps for it:

    hedge:       {launched, won, wasted, shard_reads}
    reconstruct: {calls, healthy_calls, rows_rebuilt, bytes_rebuilt,
                  patterns_seen, matrix_cache: {hit, miss}}
    spans:       seam_matrix (host: pick the survivors, look up or invert)

A program without a counter (a commit before it) reads as None, and the
harness leaves the metric out of the line; so does a share whose denominator
did not move.  A count that did not move reads 0.

``hedge_reconstruct_roofline`` is listed by no cell of ``BENCHMARK.json``: a
healthy set decodes by reconstruct about once in two seconds (PERF.md, PR 27),
so a 5 s traced slice often holds none and the share has nothing to divide by.
"""

from __future__ import annotations

import readers
import roofline
import roofline_defaults
import span_readers


def _pick(ks: "dict | None", *path):
    for key in path:
        if not isinstance(ks, dict) or key not in ks:
            return None
        ks = ks[key]
    return ks


def _delta(a: "dict | None", b: "dict | None", *path) -> "float | None":
    x, y = _pick(a, *path), _pick(b, *path)
    return None if x is None or y is None else float(y - x)


def _streams(ks: "dict | None", kind: str) -> "float | None":
    rows = _pick(ks, "streams")
    if rows is None:
        return None
    return float(sum(r["streams"] for r in rows if r["kind"] == kind))


def hedged_read_share(run) -> "float | None":
    """Hedges launched per shard read launched (the hedges among them), percent."""
    reads = _delta(run.ks_open, run.ks_close, "hedge", "shard_reads")
    hedges = _delta(run.ks_open, run.ks_close, "hedge", "launched")
    return 100.0 * hedges / reads if reads and hedges is not None else None


def healthy_reconstruct_share(run) -> "float | None":
    """GETs that decoded by reconstruct though every drive of the set was
    online when the read began (a hedge or a demotion took parity), per GET
    stream, percent."""
    calls = _delta(run.ks_open, run.ks_close, "reconstruct", "healthy_calls")
    a, b = _streams(run.ks_open, "decode"), _streams(run.ks_close, "decode")
    if calls is None or a is None or b is None or b == a:
        return None
    return 100.0 * calls / (b - a)


def loss_patterns(run) -> "float | None":
    """Survivor sets first decoded from inside the window (each costs one
    matrix inversion on the host, and on a commit whose pattern is a static
    argument one compile)."""
    return _delta(run.ks_open, run.ks_close, "reconstruct", "patterns_seen")


def matrix_time(run) -> "float | None":
    """Mean wall time of one look-up of a pattern's survivors and matrix.  A
    window without one (no read decoded by reconstruct) spent nothing there and
    reads 0; a program without the span reads None."""
    if loss_patterns(run) is None:
        return None
    return span_readers.ms_per_count(run, "seam_matrix") or 0.0


def hedge_reconstruct_roofline(run) -> "float | None":
    """Device seconds of ``jit_reconstruct_words_batch`` in the traced slice
    against the least the chip could take for what the reads needed: k rows
    of every stripe read, the rebuilt rows written."""
    t = run.trace
    if not t:
        return None
    secs = sum(v for k, v in t.get("program_s", {}).items()
               if k.startswith("jit_reconstruct_words_batch"))
    a, b = run.ks_trace_open, run.ks_trace_close
    rebuilt = _delta(a, b, "reconstruct", "bytes_rebuilt")
    counted = readers._delta(
        run, lambda ks: sum(r["bytes"] for r in readers._rows(ks, "ops", op="reconstruct")),
        trace=True)
    if not secs or not counted or rebuilt is None:
        return None
    e = run.config["erasure"]
    nbytes, nops = roofline_defaults.hedged_reconstruct_cost(
        counted, rebuilt, e["data"], e["parity"])
    return 100.0 * roofline.least_seconds(run.device["kind"], nbytes, nops) / secs
