"""The arithmetic of the metrics that read the program's own spans.

The program stamps every boundary where a request waits
(``minio_tpu/utils/spans.py``) and keeps per-thread counters that
``kernel-stats`` merges into two tables:

    spans: [{role, name, count, wall_seconds, cpu_seconds}]   (cpu_seconds null on the
           leaf spans: only the spans that bound a layer read the thread's CPU clock)
    probe: {samples, late_seconds, late_max_seconds, loops: [...]}

Every reader here is a delta of those between the snapshots the harness already
takes at the window's ends (``run.ks_open`` / ``run.ks_close``).  A program
without the tables (a commit before the spans), or a span that did not move in
the window, reads as None and the harness leaves the metric out of the line.
"""

from __future__ import annotations


def _total(ks: "dict | None", name: str, field: str, role: "str | None") -> "float | None":
    if not ks or "spans" not in ks:
        return None
    values = [r[field] for r in ks["spans"]
              if r["name"] == name and (role is None or r["role"] == role)]
    return None if any(v is None for v in values) else sum(values)


def delta(run, name: str, field: str, role: "str | None" = None) -> "float | None":
    a = _total(run.ks_open, name, field, role)
    b = _total(run.ks_close, name, field, role)
    return None if a is None or b is None else b - a


def ms_per_count(run, name: str, role: "str | None" = None) -> "float | None":
    """Mean wall time of one span or wait of the name, over the window."""
    n, wall = delta(run, name, "count", role), delta(run, name, "wall_seconds", role)
    return 1e3 * wall / n if n and wall is not None else None


def run_share(run, name: str, role: "str | None" = None) -> "float | None":
    """CPU time over wall time inside the spans of the name, percent: the share
    of the time a thread was in there that it was running."""
    wall, cpu = delta(run, name, "wall_seconds", role), delta(run, name, "cpu_seconds", role)
    return 100.0 * cpu / wall if wall and cpu is not None else None


def handler_queue_wait(run) -> "float | None":
    return ms_per_count(run, "aio_queue_wait")


def handler_run_share(run) -> "float | None":
    return run_share(run, "s3_request", role="handler")


def gil_late(run) -> "float | None":
    """Mean lateness of the probe thread's 20 ms sleep over the window, ms."""
    a, b = (run.ks_open or {}).get("probe"), (run.ks_close or {}).get("probe")
    if not a or not b:
        return None
    n = b["samples"] - a["samples"]
    return 1e3 * (b["late_seconds"] - a["late_seconds"]) / n if n else None


def meta_read_run_share(run) -> "float | None":
    """Inside ``meta_read_all``: the loop of one ``xl_read_version`` a drive on the
    handler's thread.  (The loop, not each read, reads the CPU clock: a reading is
    a system call, and there are 700 reads a second.)"""
    return run_share(run, "meta_read_all")


def iopool_queue_wait(run) -> "float | None":
    return ms_per_count(run, "iopool_queue_wait")


def flush_to_launch(run) -> "float | None":
    return ms_per_count(run, "flush_to_launch")


def seam_kernel_wait(run) -> "float | None":
    return ms_per_count(run, "seam_kernel_wait")


def seam_d2h(run) -> "float | None":
    return ms_per_count(run, "seam_d2h")
