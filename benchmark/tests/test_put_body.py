"""``metrics/put_body.py`` on hand-written ``kernel-stats`` snapshots: the window's
delta of ``hashreader_read`` wall over its PUTs, and None where a window has no PUT
(the GET-only cell), where no body was read, or where the program has no spans."""
import importlib.util
import os
import types

import pytest

from conftest import BENCH


def reader():
    path = os.path.join(BENCH, "metrics", "put_body.py")
    spec = importlib.util.spec_from_file_location("metric_put_body", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def row(role, name, count, wall, cpu=None):
    return {"role": role, "name": name, "count": count, "wall_seconds": wall, "cpu_seconds": cpu}


def snap(puts, reads, read_wall, waits):
    return {"spans": [
        row("handler", "ol_put_object", puts, 1.0 * puts, 0.08 * puts),
        row("handler", "hashreader_read", reads, read_wall),
        row("other", "hashreader_read", 2, 0.5),            # an admin upload: counted, it is a body too
        row("handler", "body_read_wait", waits, 0.8 * read_wall),
        row("handler", "ol_get_object", 7 * puts, 2.0 * puts, 0.2 * puts),
    ]}


def a_run(a, b):
    return types.SimpleNamespace(ks_open=a, ks_close=b, t0=100.0, t1=145.0)


CASES = {
    # 256 PUTs of the fill before the window, 250 inside it
    "piecewise": (snap(256, 256 * 42, 146.0, 256 * 42), snap(506, 506 * 42, 289.25, 506 * 42), 573.0),
    "one-handover": (snap(256, 256 * 2, 50.0, 256), snap(506, 506 * 2, 97.5, 506), 190.0),
    "no-put-in-window": (snap(256, 512, 50.0, 256), snap(256, 512, 50.0, 256), None),
    "puts-but-no-body-read": (snap(256, 512, 50.0, 256), snap(260, 512, 50.0, 256), None),
    "no-span-tables": ({"ops": []}, {"ops": []}, None),
    "no-snapshots": (None, None, None),
}


@pytest.mark.parametrize("case", CASES)
def test_put_body(case):
    a, b, want = CASES[case]
    got = reader()(a_run(a, b))
    assert got == (want if want is None else pytest.approx(want))


def test_benchmark_json_lists_it_in_the_mixed_cells():
    import json

    from conftest import REPO

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [m for m in bench["per_layer"] if m["name"] == "put_body"]
    assert entry == [{
        "name": "put_body", "unit": "ms/PUT", "better": "lower", "source": "program_span",
        "layer": "served request", "moves": "op_rate",
        "workloads": ["mixed-10m", "mixed-10m-defaults"],
    }]
    assert {m["layer"] for m in bench["per_layer"]} >= {"served request"}
