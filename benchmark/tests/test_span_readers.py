"""The readers of the program's own spans (``span_readers.py``): each on two
hand-written ``kernel-stats`` snapshots - the delta, the division by the count,
None when nothing moved or the program has no such table - and, in whole runs of
the CPU rehearsal, all eight metrics in the line of both cells."""
import types

import pytest
import span_readers as S
from test_run_end_to_end import rehearse

NEW = ["handler_queue_wait", "handler_run_share", "gil_late", "meta_read_run_share",
       "iopool_queue_wait", "flush_to_launch", "seam_kernel_wait", "seam_d2h"]


def row(role, name, count, wall, cpu=0.0):
    return {"role": role, "name": name, "count": count, "wall_seconds": wall, "cpu_seconds": cpu}


OPEN = {
    "spans": [
        row("handler", "aio_queue_wait", 100, 10.0),
        row("other", "aio_queue_wait", 5, 0.001),
        row("handler", "s3_request", 100, 40.0, 4.0),
        row("other", "s3_request", 3, 900.0, 0.1),       # an admin trace stream: not a handler
        row("handler", "meta_read_all", 100, 20.0, 2.0),
        row("other", "meta_read_all", 10, 1.0, 0.5),
        row("handler", "xl_read_version", 1200, 19.0, None),   # a leaf span: wall only
        row("iopool", "iopool_queue_wait", 1000, 0.5),
        row("batcher", "flush_to_launch", 50, 0.1),
        row("batcher", "seam_kernel_wait", 50, 0.05, None),
        row("iopool", "seam_d2h", 10, 0.01, None),
        row("batcher", "seam_stage", 50, 0.2, None),     # read by no metric
    ],
    "probe": {"samples": 1000, "late_seconds": 2.0, "late_max_seconds": 0.3, "loops": []},
}
CLOSE = {
    "spans": [
        row("handler", "aio_queue_wait", 300, 40.0),
        row("other", "aio_queue_wait", 5, 0.001),
        row("handler", "s3_request", 300, 140.0, 19.0),
        row("other", "s3_request", 4, 1900.0, 0.2),
        row("handler", "meta_read_all", 300, 60.0, 4.0),
        row("other", "meta_read_all", 30, 11.0, 1.5),
        row("handler", "xl_read_version", 3600, 57.0, None),
        row("iopool", "iopool_queue_wait", 5000, 2.5),
        row("batcher", "flush_to_launch", 150, 0.4),
        row("batcher", "seam_kernel_wait", 100, 0.15, None),
        row("iopool", "seam_kernel_wait", 50, 0.05, None),  # a name new to a role
        row("iopool", "seam_d2h", 30, 0.07, None),
        row("batcher", "seam_stage", 50, 0.2, None),
    ],
    "probe": {"samples": 3000, "late_seconds": 12.0, "late_max_seconds": 0.4, "loops": []},
}
WANT = {
    "handler_queue_wait": 1e3 * 30.0 / 200,        # both roles: every request that queued
    "handler_run_share": 100.0 * 15.0 / 100.0,     # the handler threads only
    "gil_late": 1e3 * 10.0 / 2000,
    "meta_read_run_share": 100.0 * (2.0 + 1.0) / (40.0 + 10.0),
    "iopool_queue_wait": 1e3 * 2.0 / 4000,
    "flush_to_launch": 1e3 * 0.3 / 100,
    "seam_kernel_wait": 1e3 * 0.15 / 100,
    "seam_d2h": 1e3 * 0.06 / 20,
}


def a_run(a, b):
    return types.SimpleNamespace(ks_open=a, ks_close=b, t0=100.0, t1=145.0)


@pytest.mark.parametrize("name", NEW)
def test_reader_is_a_delta_over_the_count(name):
    got = getattr(S, name)(a_run(OPEN, CLOSE))
    assert got == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_none_when_nothing_moved(name):
    assert getattr(S, name)(a_run(CLOSE, CLOSE)) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_none_from_a_program_without_the_tables(name):
    """The parent commit has no spans: the reader may not raise there."""
    old = {"ops": [], "batch": {"flushes": 1, "jobs": 1, "blocks": 1, "wait_seconds": 0.0}}
    assert getattr(S, name)(a_run(old, old)) is None
    assert getattr(S, name)(a_run(None, None)) is None


@pytest.mark.parametrize("name", NEW)
def test_metric_file_calls_its_reader(name):
    import run as harness

    assert harness.read_metric(name, a_run(OPEN, CLOSE)) == pytest.approx(WANT[name])


def test_benchmark_json_lists_the_eight_in_both_cells():
    import json
    import os

    from conftest import REPO

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == NEW
    for m in tail:
        assert m["source"] == "program_counter"
        assert m["workloads"] == ["mixed-10m", "get-degraded-10m"]
        assert m["moves"] in ("op_rate", "payload_rate")


@pytest.mark.parametrize("workload", ["mixed-10m", "get-degraded-10m"])
def test_traced_rehearsal_prints_all_eight(workload):
    """The degraded GET reads metadata, shards through the iopool, and
    reconstructs through the batcher and the seam: neither cell leaves one out."""
    line = rehearse(workload, "--trace", "1")
    assert line["correct"] is True
    for name in NEW:
        value = line["metrics"][name]["value"]
        assert value == value and 0 <= value < 1e9, (name, value)


def test_run_share_of_a_wall_only_span_is_none():
    assert S.run_share(a_run(OPEN, CLOSE), "xl_read_version") is None
    assert S.ms_per_count(a_run(OPEN, CLOSE), "xl_read_version") == pytest.approx(1e3 * 38.0 / 2400)
