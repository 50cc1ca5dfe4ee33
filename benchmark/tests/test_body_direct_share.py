"""``metrics/body_direct_share.py`` on hand-written ``kernel-stats`` snapshots: the
window's share of body bytes that came straight from the socket, and None where the
program has no ``direct`` key (a commit before the counter), where no body was read in the window, or
where a snapshot is missing."""
import importlib.util
import json
import os
import types

import pytest

from conftest import BENCH, REPO


def reader():
    path = os.path.join(BENCH, "metrics", "body_direct_share.py")
    spec = importlib.util.spec_from_file_location("metric_body_direct_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def snap(handovers, nbytes, loop_reads, direct=None):
    body = {"handovers": handovers, "bytes": nbytes, "loop_reads": loop_reads}
    if direct is not None:
        body["direct"] = direct
    return {"body_read": body, "spans": []}


def a_run(a, b):
    return types.SimpleNamespace(ks_open=a, ks_close=b, t0=100.0, t1=145.0)


MIB = 1 << 20
CASES = {
    # the fill's 256 PUTs before the window (all staged there), 40 in it
    "window-delta": (snap(256, 2560 * MIB, 256 * 41, 0),
                     snap(296, 2960 * MIB, 256 * 41 + 200, 384 * MIB), 96.0),
    "all-staged": (snap(10, 10 * MIB, 10, 0), snap(20, 20 * MIB, 20, 0), 0.0),
    "no-direct-key": (snap(256, 2560 * MIB, 10496), snap(296, 2960 * MIB, 12136), None),
    "no-body-in-window": (snap(256, MIB, 300, MIB // 2), snap(256, MIB, 300, MIB // 2), None),
    "no-counter": ({"spans": []}, {"spans": []}, None),
    "no-snapshots": (None, None, None),
}


@pytest.mark.parametrize("case", CASES)
def test_body_direct_share(case):
    a, b, want = CASES[case]
    got = reader()(a_run(a, b))
    assert got == (want if want is None else pytest.approx(want))


def test_benchmark_json_lists_it_where_put_body_is():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    layers = {m["name"]: m for m in bench["per_layer"]}
    m = layers["body_direct_share"]
    assert m == {"name": "body_direct_share", "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "served request",
                 "moves": "op_rate", "workloads": layers["put_body"]["workloads"]}
