"""The arithmetic of the metrics that account a request's wall and the server's
CPU: each to 100 %.

Four tables of ``kernel-stats`` (``minio_tpu/utils/spans.py``, PR 35), read as
window deltas between the snapshots the harness already takes (``run.ks_open`` /
``run.ks_close``):

    requests: [{verb, count, wall_seconds, cpu_seconds, queue_wait_seconds,
                self: {span: [count, seconds]}}]
        by S3 API call (PutObject, GetObject, HeadObject, DeleteObject, ...; other),
        on the request's own thread: the root's wall and CPU, and every span's SELF
        time there - its wall less its children's - so a verb's ``self`` adds up to
        its ``wall_seconds``.  The root's own and the four ``ol_*`` spans' are the
        time inside no named child.
    fanout:   {put_flush | put_close | put_rename | get_reads:
               {count, wall_seconds, last_queue_seconds, last_run_seconds}}
        a wait for drive jobs that ran abreast, with the queue wait and the run of
        the ONE job whose completion ended it (a sum over the jobs would count twelve
        that ran side by side twelve times).
    cpu:      {<role>: seconds, ..., process_seconds}
        the scheduler's account of every thread of the process by the role its name
        gives it (handler, loop, iopool, batcher, warmer, crawler, probe, other;
        native: what no Python thread burnt - the runtime's own threads), read when
        the snapshot is taken.
    loops:    [{loop, requests, queue_wait_seconds}]
        the handler threads' counters by the event loop they belong to.

A program without a table (a commit before it), or a denominator that did not move
in the window, reads as None and the harness leaves the metric out of the line.
"""

from __future__ import annotations

import span_readers

BLIND = ("s3_request", "ol_put_object", "ol_get_object", "ol_get_object_info",
         "ol_delete_object")
PUT_PHASES = ("put_flush", "put_close", "put_rename")


def _tables(run, name: str, kind: type) -> "tuple | None":
    """The table at the window's two ends, or None where either side lacks it."""
    out = tuple(ks.get(name) if isinstance(ks, dict) else None
                for ks in (run.ks_open, run.ks_close))
    return out if all(isinstance(t, kind) for t in out) else None


def _requests(run) -> "tuple[dict, dict] | None":
    both = _tables(run, "requests", list)
    return both and tuple({r["verb"]: r for r in rows} for rows in both)


def _moved(run, field: str, verb: "str | None" = None) -> "float | None":
    """The window's delta of a field of ``requests``, one verb's or all verbs'."""
    both = _requests(run)
    if both is None:
        return None
    a, b = both
    return sum(r[field] - a.get(v, {}).get(field, 0)
               for v, r in b.items() if verb is None or v == verb)


def _self_moved(run, names) -> "float | None":
    both = _requests(run)
    if both is None:
        return None
    a, b = both
    return sum(r["self"][n][1] - a.get(v, {}).get("self", {}).get(n, (0, 0.0))[1]
               for v, r in b.items() for n in names if n in r["self"])


def _table_moved(run, table: str, key: str, field: "str | None" = None) -> "float | None":
    """The delta of ``ks[table][key]`` (or of its ``field``); None without the table,
    0 from a row that the window's first snapshot did not have yet."""
    both = _tables(run, table, dict)
    if both is None or key not in both[1]:
        return None
    a, b = both
    if field is None:
        return b[key] - a.get(key, 0)
    return b[key][field] - a.get(key, {}).get(field, 0)


def _per(total: "float | None", n: "float | None", scale: float = 1e3) -> "float | None":
    return scale * total / n if total is not None and n else None


def request_cpu(run) -> "float | None":
    """CPU of the handler's thread inside the root span, ms a request, all verbs."""
    return _per(_moved(run, "cpu_seconds"), _moved(run, "count"))


def unspanned_share(run) -> "float | None":
    """Percent of the requests' wall that is the self time of the root and of the
    four ``ol_*`` spans: time on the request's thread inside no named child."""
    return _per(_self_moved(run, BLIND), _moved(run, "wall_seconds"), 100.0)


def _put_phases(run, field: str) -> "float | None":
    parts = [_table_moved(run, "fanout", p, field) for p in PUT_PHASES]
    return None if any(p is None for p in parts) else sum(parts)


def put_drive_wait(run) -> "float | None":
    """ms a PUT spent waiting for its three drive fan-outs (the batches' flushes to
    quorum, the writers' close, rename_data), each by its wall."""
    return _per(_put_phases(run, "wall_seconds"), _moved(run, "count", "PutObject"))


def put_straggler_queue(run) -> "float | None":
    """Of that, ms a PUT that the job which ended each wait had spent in its
    drive's queue before a worker took it: the hand-off, not the drive."""
    return _per(_put_phases(run, "last_queue_seconds"), _moved(run, "count", "PutObject"))


def get_drive_wait(run) -> "float | None":
    """ms a GET spent waiting for the shard reads of its block groups."""
    return _per(_table_moved(run, "fanout", "get_reads", "wall_seconds"),
                _moved(run, "count", "GetObject"))


def cpu_per_request(run, row: str) -> "float | None":
    """ms of CPU a request that the scheduler charged the threads of one role, or
    the whole process (``process_seconds``), over the window's ``s3_request``."""
    return _per(_table_moved(run, "cpu", row), span_readers.delta(run, "s3_request", "count"))


def loop_skew(run) -> "float | None":
    """The busiest loop's requests x the number of loops / all requests: 1 where
    the connections spread evenly, the number of loops where one took them all."""
    both = _tables(run, "loops", list)
    if both is None:
        return None
    before = {r["loop"]: r["requests"] for r in both[0]}
    moved = [r["requests"] - before.get(r["loop"], 0) for r in both[1]]
    return max(moved) * len(moved) / sum(moved) if sum(moved) else None
