"""``heal_parked_share`` on hand-written ``kernel-stats.heal`` snapshots: the
window's pushes the MRF queue set aside or absorbed over all its pushes;
None on a program without ``heal.parked`` (a commit before it), where no
push moved, or where a snapshot is missing; and its entry in
``BENCHMARK.json``."""
import importlib.util
import json
import os
import types

import pytest

from conftest import BENCH, REPO

NAME = "heal_parked_share"
CELLS = ["mixed-10m-16d-3lost", "get-degraded-10m"]


def read(a, b):
    path = os.path.join(BENCH, "metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + NAME, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(types.SimpleNamespace(ks_open=a, ks_close=b, t0=100.0, t1=145.0))


def snap(partial=0, degraded=0, bitrot=0, parked=None):
    heal = {"pushed": {"partial_write": partial, "bitrot_read": bitrot,
                       "degraded_read": degraded, "crawl": 0},
            "tasks": {"healed": 0, "unwritable": 0, "clean": 0, "failed": 0},
            "seconds": 0.0, "read_bytes": 0, "written_bytes": 0, "queue_depth": 0}
    if parked is not None:
        heal["parked"] = dict(zip(("set_aside", "absorbed", "requeued"), parked))
        heal["parked_depth"] = 0
    return {"heal": heal}


CASES = {
    # 1,000 pushes in the window (250 short PUTs, 740 degraded GETs, 10
    # bitrot reads), 10 set aside and 980 absorbed; re-queues are no push
    "window-delta": (snap(100, 300, 0, (200, 100, 0)),
                     snap(350, 1040, 10, (210, 1080, 40)), 99.0),
    "none-parked": (snap(1, 1, parked=(0, 0, 0)), snap(5, 7, parked=(0, 0, 3)), 0.0),
    "no-push": (snap(4, 4, parked=(1, 7, 0)), snap(4, 4, parked=(1, 7, 2)), None),
    "no-parked-key": (snap(100, 300), snap(350, 1050), None),
    "parked-on-one-side": (snap(100, 300), snap(350, 1050, parked=(5, 5, 0)), None),
    "no-heal-table": ({}, {}, None),
    "missing-snapshot": (None, None, None),
}


@pytest.mark.parametrize("case", CASES)
def test_reader(case):
    a, b, want = CASES[case]
    got = read(a, b)
    assert got == (want if want is None else pytest.approx(want))


def test_benchmark_json_lists_it_in_the_lost_drive_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert m == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
                 "layer": "background heal", "moves": "op_rate", "workloads": CELLS}
    cells = {w["name"] for w in bench["workloads"]}
    assert set(CELLS) <= cells
