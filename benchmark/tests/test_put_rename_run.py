"""``put_rename_run`` (PR 36) on a made-up ``fanout`` table: the window's delta of
``put_rename.last_run_seconds`` over that of its ``count``, None where the count did
not move or the program has no such table, and its ``BENCHMARK.json`` entry by name."""
import json
import os
import types

import pytest
from conftest import BENCH, REPO


def phase(count, wall, queue, run):
    return {"count": count, "wall_seconds": wall, "last_queue_seconds": queue,
            "last_run_seconds": run}


def a_run(a, b):
    return types.SimpleNamespace(ks_open=a, ks_close=b)


def fanout(count, run, **more):
    return {"fanout": {"put_rename": phase(count, 9.0, 1.0, run), **more}}


def read(run):
    import run as harness

    return harness.read_metric("put_rename_run", run)


def test_it_is_the_last_jobs_run_a_rename_wait_in_ms():
    # 10 waits in the window, whose last jobs ran 0.25 s in all
    assert read(a_run(fanout(5, 0.30), fanout(15, 0.55))) == pytest.approx(25.0)
    # the other phases do not enter it
    noisy = fanout(15, 0.55, put_close=phase(15, 4.0, 3.0, 2.0))
    assert read(a_run(fanout(5, 0.30), noisy)) == pytest.approx(25.0)
    # a phase that the window's first snapshot had not seen counts from zero
    assert read(a_run({"fanout": {}}, fanout(4, 0.1))) == pytest.approx(25.0)


@pytest.mark.parametrize("run", [
    a_run(fanout(5, 0.30), fanout(5, 0.30)),           # no PUT in the window
    a_run({"spans": []}, {"spans": []}),               # a program before the table
    a_run(fanout(5, 0.30), {"fanout": {}}),            # the phase is gone
    a_run(None, None), a_run({}, {}),
], ids=["count-did-not-move", "no-table", "no-phase", "no-snapshot", "empty"])
def test_it_reads_none_and_does_not_raise(run):
    assert read(run) is None


def test_benchmark_json_names_it_beside_the_other_put_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    layers = {m["name"]: m for m in bench["per_layer"]}  # by name, wherever it stands
    m = layers["put_rename_run"]
    assert m == {"name": "put_rename_run", "unit": "ms/PUT", "better": "lower",
                 "source": "program_span", "layer": "drives", "moves": "op_rate",
                 "workloads": layers["put_drive_wait"]["workloads"]}
    assert "get-degraded-10m" not in m["workloads"] and len(m["workloads"]) == 4
    assert m["layer"] in {x["layer"] for x in bench["per_layer"] if x is not m}
    assert os.path.isfile(os.path.join(BENCH, "metrics", "put_rename_run.py"))
