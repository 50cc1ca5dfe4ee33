"""``metrics/delete_drive.py`` on hand-written ``kernel-stats`` snapshots: the window's
delta of ``xl_delete_file`` wall over its count, on whatever thread the drive was asked,
and None where a window removed nothing (the GET-only cell) or the program has no spans."""
import importlib.util
import json
import os
import types

import pytest

from conftest import BENCH, REPO


def reader():
    path = os.path.join(BENCH, "metrics", "delete_drive.py")
    spec = importlib.util.spec_from_file_location("metric_delete_drive", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def row(role, name, count, wall, cpu=None):
    return {"role": role, "name": name, "count": count, "wall_seconds": wall, "cpu_seconds": cpu}


def snap(removals, wall, on_iopool=0):
    return {"spans": [
        row("handler", "xl_delete_file", removals, wall),
        row("iopool", "xl_delete_file", on_iopool, 0.002 * on_iopool),  # a fan-out would land here
        row("handler", "ol_delete_object", removals // 12, 1.1 * wall, 0.1 * wall),
        row("handler", "xl_rename_data", 300, 16.0),
    ]}


def a_run(a, b):
    return types.SimpleNamespace(ks_open=a, ks_close=b, t0=100.0, t1=145.0)


CASES = {
    # 45 s of mixed-10m: some 200 DELETEs and 100 overwriting PUTs, 12 drives each
    "walked": (snap(120, 3.84), snap(3720, 119.04), 32.0),
    "by-name": (snap(120, 0.6), snap(3720, 18.6), 5.0),
    "two-roles-add-up": (snap(120, 0.6, 0), snap(3720, 18.6, 3600), 3.5),
    "nothing-removed-in-window": (snap(120, 3.84), snap(120, 3.84), None),
    "no-span-tables": ({"ops": []}, {"ops": []}, None),
    "no-snapshots": (None, None, None),
}


@pytest.mark.parametrize("case", CASES)
def test_delete_drive(case):
    a, b, want = CASES[case]
    got = reader()(a_run(a, b))
    assert got == (want if want is None else pytest.approx(want))


def test_benchmark_json_lists_it_where_something_is_removed():
    # by name, wherever it stands in the list: a later PR appends after it
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "delete_drive"]
    assert entry["unit"] == "ms/call" and entry["better"] == "lower"
    assert entry["source"] == "program_span" and entry["moves"] == "op_rate"
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] == "iopool_busy"}
    mixed = {w["name"] for w in bench["workloads"] if w["traffic"].startswith("mixed")}
    assert set(entry["workloads"]) == mixed  # a cell that sends DELETE and PUT; not the GET-only one
    reports = {m["name"]: set(m.get("workloads", ())) for m in bench["end_to_end"]}
    assert set(entry["workloads"]) <= reports[entry["moves"]]
