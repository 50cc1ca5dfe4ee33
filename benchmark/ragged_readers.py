"""The arithmetic of the metrics of the deployment whose objects have every
size (``ec8p4-12d-randsize``): what the width ladder of the codec seam costs and
whether the Pallas kernels run on ragged shards, and the GET medians at the two
ends of the size range.

The program stages every launch at a rung of its width ladder (whole kernel
tiles) and passes each row's true length as an operand.  Three readers are
window deltas of ``kernel-stats`` between the snapshots the harness already
takes (``run.ks_open`` / ``run.ks_close``), of counters the program keeps:

    ragged: {launches, rows, true_bytes, staged_bytes, mixed_launches,
             widths_true, widths_staged, staged_rows: {width: rows}}
    device_passes / pallas_passes: launches by entry point

``ragged`` counts the launches of the served entry points that take lengths
(encode_words_fused1, digest_words; the decode is column-wise and takes none):
their real rows,
the callers' shard bytes, the bytes of the width the rows were staged at
(padding ROWS are not in it: they are in ``h2d``, which ``bus_ratio`` reads).
A program without the counter (a commit before it) reads as None, and the
harness leaves the metric out of the line; so does a ratio whose denominator
did not move.  The two medians are the generator's clock over ``run.records``.
"""

from __future__ import annotations

import statistics

import readers

# run.py::PALLAS_KERNELS: the entry points that have a Pallas form (a test holds
# the two tuples equal; run.py is the harness's main module and is not imported)
PALLAS_KERNELS = ("encode_words_fused1", "reconstruct_words_batch",
                  "mesh_encode_hash", "mesh_reconstruct")
ENDS = 3  # sizes at each end of the range that "small" and "large" stand for


def _ragged(ks: "dict | None") -> "dict | None":
    r = ks.get("ragged") if isinstance(ks, dict) else None
    return r if isinstance(r, dict) else None


def pad_ratio(run) -> "float | None":
    """Bytes staged to the device per byte of shard the callers brought, over
    the window's launches: 1.0 where every row lies on a rung of the ladder."""
    a, b = _ragged(run.ks_open), _ragged(run.ks_close)
    if a is None or b is None:
        return None
    true = b["true_bytes"] - a["true_bytes"]
    return (b["staged_bytes"] - a["staged_bytes"]) / true if true else None


def staged_widths(run) -> "float | None":
    """Distinct staged widths launched inside the window: each is a family of
    programs (one a row count), and rows of any length it holds share them."""
    a, b = _ragged(run.ks_open), _ragged(run.ks_close)
    if a is None or b is None:
        return None
    return float(sum(1 for w, n in b["staged_rows"].items()
                     if n != a["staged_rows"].get(w, 0)))


def pallas_share(run) -> "float | None":
    """Share of the window's launches of the entry points that have a Pallas
    form which ran it (the rest took the portable XLA form), percent."""
    a, b = run.ks_open, run.ks_close
    if not a or not b or "device_passes" not in b or "pallas_passes" not in b:
        return None

    def moved(table: str) -> int:
        return sum(b[table].get(k, 0) - a.get(table, {}).get(k, 0) for k in PALLAS_KERNELS)

    passes = moved("device_passes")
    return 100.0 * moved("pallas_passes") / passes if passes else None


def _get_p50(run, sizes: "list[int]") -> "float | None":
    v = [(r.end - r.start) * 1e3 for r in readers.ended_in_window(run, "GET")
         if not r.failed and not r.wrong and r.nbytes in sizes]
    return statistics.median(v) if v else None


def _sizes(run) -> "list[int]":
    return sorted({int(s) for s, _ in run.traffic["sizes"]})


def get_p50_small(run) -> "float | None":
    """Client median of the GETs of the three smallest sizes: what a small
    object pays for a launch staged at whole tiles."""
    sizes = _sizes(run)
    return _get_p50(run, sizes[:ENDS]) if len(sizes) >= 2 * ENDS else None


def get_p50_large(run) -> "float | None":
    """Client median of the GETs of the three largest sizes."""
    sizes = _sizes(run)
    return _get_p50(run, sizes[-ENDS:]) if len(sizes) >= 2 * ENDS else None
