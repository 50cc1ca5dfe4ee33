"""The arithmetic of the metrics of the deployment whose objects are many
erasure blocks long (``ec8p4-12d-64m``): how many blocks a stream carries, what
the GET's handler waits for its read-ahead, how long a GET takes to hand its
first body bytes to the response writer, and the largest launch the codec seam
made.

All four are window deltas of ``kernel-stats`` between the snapshots the harness
already takes (``run.ks_open`` / ``run.ks_close``), of counters and spans the
program keeps:

    stream: {encode: {streams, blocks, batches, tail_groups}, decode: {...}}
    launch: {count, bytes, max_bytes, split_calls, sizes: {input bytes: launches}}
    spans:  stream_readahead_wait (a wait), get_first_write

``launch.sizes`` counts the launches of the three served entry points by the
bytes of their input; the seam's two ladders bound its keys, and a window's
largest launch is the largest key whose count moved between two snapshots
(``max_bytes`` alone is since boot).  A program without the counters (a commit
before them) reads as None, and the harness leaves the metric out of the line;
so does a ratio whose denominator did not move.
"""

from __future__ import annotations

import span_readers

MIB = float(1 << 20)


def _table(ks: "dict | None", name: str) -> "dict | None":
    t = ks.get(name) if isinstance(ks, dict) else None
    return t if isinstance(t, dict) else None


def stream_blocks(run) -> "float | None":
    """Erasure blocks per stream, both directions, over the window: 1.0 where
    every object is one block, 7.0 where every one is 64 MiB."""
    a, b = _table(run.ks_open, "stream"), _table(run.ks_close, "stream")
    if a is None or b is None:
        return None

    def moved(field: str) -> int:
        return sum(b[d][field] - a.get(d, {}).get(field, 0) for d in b)

    streams = moved("streams")
    return moved("blocks") / streams if streams else None


def readahead_wait(run) -> "float | None":
    """Mean wait of a GET's handler for the batch its read-ahead is decoding."""
    return span_readers.ms_per_count(run, "stream_readahead_wait")


def get_first_write(run) -> "float | None":
    """Mean time from ``ol_get_object``'s start to the first body bytes handed
    to the response writer."""
    return span_readers.ms_per_count(run, "get_first_write")


def launch_peak(run) -> "float | None":
    """The largest input of one launch inside the window, MiB."""
    a, b = _table(run.ks_open, "launch"), _table(run.ks_close, "launch")
    if a is None or b is None:
        return None
    moved = [int(size) for size, n in b["sizes"].items() if n != a["sizes"].get(size, 0)]
    return max(moved) / MIB if moved else None
