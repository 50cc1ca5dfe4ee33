"""``metrics/info_outside_round.py`` on hand-written ``kernel-stats`` snapshots: the window's
mean wall of ``ol_get_object_info`` less its mean wall of a round of ``meta_read_all``, and
None where a window has no STAT (the GET-only cell), no round, or the program no spans."""
import importlib.util
import json
import os
import types

import pytest

from conftest import BENCH, REPO


def reader():
    path = os.path.join(BENCH, "metrics", "info_outside_round.py")
    spec = importlib.util.spec_from_file_location("metric_info_outside_round", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def row(role, name, count, wall, cpu=None):
    return {"role": role, "name": name, "count": count, "wall_seconds": wall, "cpu_seconds": cpu}


def snap(stats, info_wall, rounds, round_wall, crawler_rounds=0):
    return {"spans": [
        row("handler", "ol_get_object_info", stats, info_wall, 0.1 * info_wall),
        row("handler", "meta_read_all", rounds, round_wall, 0.2 * round_wall),
        row("other", "meta_read_all", crawler_rounds, 0.056 * crawler_rounds),  # roles add up
        row("handler", "ol_get_object", 900, 300.0, 25.0),
    ]}


def a_run(a, b):
    return types.SimpleNamespace(ks_open=a, ks_close=b, t0=100.0, t1=145.0)


CASES = {
    # 45 s of mixed-10m: some 600 STATs among 2,100 requests of one round each
    "three-snapshots-a-stat": (snap(30, 2.85, 100, 5.6), snap(630, 59.85, 2200, 123.2), 39.0),
    "none": (snap(30, 1.83, 100, 5.6), snap(630, 38.43, 2200, 123.2), 5.0),
    # a STAT's own round can be shorter than the mean round of the window
    "below-the-mean-round": (snap(30, 1.5, 100, 5.6), snap(630, 31.5, 2200, 123.2), -6.0),
    "every-role-of-the-round": (snap(30, 1.83, 100, 5.6), snap(630, 38.43, 1150, 64.4, 1050), 5.0),
    "no-stat-in-window": (snap(30, 1.83, 100, 5.6), snap(30, 1.83, 2200, 123.2), None),
    "no-round-in-window": (snap(30, 1.83, 100, 5.6), snap(630, 38.43, 100, 5.6), None),
    "no-span-tables": ({"ops": []}, {"ops": []}, None),
    "no-snapshots": (None, None, None),
}


@pytest.mark.parametrize("case", CASES)
def test_info_outside_round(case):
    a, b, want = CASES[case]
    got = reader()(a_run(a, b))
    assert got == (want if want is None else pytest.approx(want))


def test_benchmark_json_lists_it_where_a_stat_is_sent():
    # by name, wherever it stands in the list: a later PR appends after it
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "info_outside_round"]
    assert entry["unit"] == "ms/call" and entry["better"] == "lower"
    assert entry["source"] == "program_span" and entry["moves"] == "op_rate"
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] == "meta_round"}
    mixed = {w["name"] for w in bench["workloads"] if w["traffic"].startswith("mixed")}
    assert set(entry["workloads"]) == mixed  # a cell that sends STAT; not the GET-only one
    reports = {m["name"]: set(m.get("workloads", ())) for m in bench["end_to_end"]}
    assert set(entry["workloads"]) <= reports[entry["moves"]]
