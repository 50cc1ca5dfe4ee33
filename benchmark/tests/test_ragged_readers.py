"""The readers of the deployment whose objects have every size
(``ragged_readers.py``) on a recorded ``kernel-stats`` pair: the window's delta,
the division, None where the program keeps no such counter (the parent commit);
the two GET medians on records; the configuration's file against the one it
differs from, and what ``BENCHMARK.json`` gained."""
import json
import os
import types

import generator as G
import pytest
import ragged_readers as R
from conftest import BENCH, HERE, REPO

with open(os.path.join(HERE, "data", "ragged.kernel-stats.json")) as f:
    RECORDED = json.load(f)
OPEN, CLOSE = RECORDED["open"], RECORDED["close"]
FIVE = ["pad_ratio", "staged_widths", "pallas_share", "get_p50_small", "get_p50_large"]
with open(os.path.join(BENCH, "traffic", "mixed-randsize.json")) as f:
    TRAFFIC = json.load(f)
SIZES = [s for s, _ in TRAFFIC["sizes"]]


def record(kind, nbytes, ms, end=120.0, failed=False):
    return G.Record(0, kind, "k", end - ms / 1e3, end - ms / 1e3, end, 200, failed, False,
                    0 if failed else nbytes)


RECORDS = ([record("GET", SIZES[j], 100.0 + 10 * j) for j in range(16)]
           + [record("GET", SIZES[0], 9999.0, end=99.0),  # before the window
              record("GET", SIZES[15], 1.0, failed=True), record("PUT", SIZES[0], 5.0)])


def a_run(a, b, records=RECORDS, traffic=TRAFFIC):
    return types.SimpleNamespace(ks_open=a, ks_close=b, records=records, traffic=traffic,
                                 t0=100.0, t1=145.0)


def want() -> dict:
    a, b = OPEN["ragged"], CLOSE["ragged"]
    moved = lambda t: sum(CLOSE[t].get(k, 0) - OPEN[t].get(k, 0) for k in R.PALLAS_KERNELS)
    return {
        "pad_ratio": (b["staged_bytes"] - a["staged_bytes"]) / (b["true_bytes"] - a["true_bytes"]),
        "staged_widths": 5.0,  # 1, 2, 3, 10 and 20 tiles
        "pallas_share": 100.0 * moved("pallas_passes") / moved("device_passes"),
        "get_p50_small": 110.0, "get_p50_large": 240.0,
    }


def test_the_recording_moved_every_counter_the_readers_read():
    a, b = OPEN["ragged"], CLOSE["ragged"]
    assert b["launches"] - a["launches"] == 12 and a["launches"] == 4  # the decode is not in it
    assert b["widths_true"] == 8 and b["widths_staged"] == 5 and a["widths_staged"] == 1
    assert set(b["staged_rows"]) == {str(t * 16384) for t in (1, 2, 3, 10, 20)}
    assert 1.0 < want()["pad_ratio"] < 1.25
    assert want()["pallas_share"] == 100.0  # interpreted: every width took the kernels
    assert CLOSE["portable_passes"] == {"digest_words": 8}  # the digest has no Pallas form


@pytest.mark.parametrize("name", FIVE)
def test_reader_is_the_windows_delta_and_its_metric_file_calls_it(name):
    import run as harness

    run = a_run(OPEN, CLOSE)
    assert getattr(R, name)(run) == pytest.approx(want()[name], rel=1e-9)
    assert harness.read_metric(name, run) == pytest.approx(want()[name], rel=1e-9)
    assert R.PALLAS_KERNELS == harness.PALLAS_KERNELS


@pytest.mark.parametrize("name", FIVE[:3])
def test_reader_reads_none_from_a_program_without_the_counters(name):
    """The parent commit keeps no ``ragged`` table: ``pad_ratio`` and
    ``staged_widths`` read None there and may not raise; ``pallas_share`` reads
    its passes, which the parent has (and took the portable form for)."""
    old_a = {k: v for k, v in OPEN.items() if k != "ragged"}
    old_b = {k: v for k, v in CLOSE.items() if k != "ragged"}
    got = getattr(R, name)(a_run(old_a, old_b))
    assert got == (want()[name] if name == "pallas_share" else None)
    assert getattr(R, name)(a_run(None, None)) is None
    assert getattr(R, name)(a_run({}, {})) is None
    portable = dict(old_b, pallas_passes=old_a["pallas_passes"])
    assert R.pallas_share(a_run(old_a, portable)) == 0.0


def test_a_window_in_which_nothing_moved():
    run = a_run(CLOSE, CLOSE, records=[])
    assert R.staged_widths(run) == 0.0  # a count
    for name in ("pad_ratio", "pallas_share", "get_p50_small", "get_p50_large"):
        assert getattr(R, name)(run) is None  # a ratio or a median of nothing is not
    one_size = dict(TRAFFIC, sizes=[[10485760, 1]])
    assert R.get_p50_small(a_run(OPEN, CLOSE, traffic=one_size)) is None


def test_aligned_rows_read_a_ratio_of_one():
    """`mixed-10m` lists `pad_ratio` as the control: 80 tiles is a rung."""
    b = json.loads(json.dumps(OPEN))
    b["ragged"]["true_bytes"] += 96 * 1310720
    b["ragged"]["staged_bytes"] += 96 * 1310720
    assert R.pad_ratio(a_run(OPEN, b)) == 1.0


def test_the_configuration_differs_from_the_defaults_in_the_objects_sizes_only():
    with open(os.path.join(BENCH, "configs", "ec8p4-12d-defaults.json")) as f:
        control = json.load(f)
    with open(os.path.join(BENCH, "configs", "ec8p4-12d-randsize.json")) as f:
        config = json.load(f)
    assert "env" not in config and config["server_args"] == ["--parity", "4"]
    for key in ("nodes", "sets", "drives_per_set", "chips", "placement", "erasure",
                "server_args", "guarantees", "compared"):
        assert config[key] == control[key], key
    assert set(config) - set(control) == {"differs_from_ec8p4-12d-defaults", "object_sizes"}
    assert list(config["reduced"]) == ["pool_objects"]
    assert {"size_span", "size_quantiles"} <= set(config["assumed"])
    assert config["name"] == "ec8p4-12d-randsize" and config["source"] != control["source"]


def test_benchmark_json_gained_one_configuration_one_cell_and_the_five():
    """By name, not by position: a later PR appends after these."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == "mixed-randsize")
    assert cell == dict(cell, config="ec8p4-12d-randsize", traffic="mixed-randsize",
                        chips=1) and len(cell["why"]) <= 200
    conf = next(c for c in bench["configs"] if c["name"] == "ec8p4-12d-randsize")
    assert conf["file"] == "benchmark/configs/ec8p4-12d-randsize.json"
    assert conf["reduced"] == ["pool_objects"] and len(conf["source"]) <= 200
    with open(os.path.join(REPO, conf["file"])) as f:
        assert json.load(f)["source"] == conf["source"]
    layers = {m["name"]: m for m in bench["per_layer"]}
    assert [layers[n]["workloads"][:2] for n in FIVE] == (
        [["mixed-randsize", "mixed-10m"]] + 4 * [["mixed-randsize"]])
    assert [layers[n]["layer"] for n in FIVE] == (
        2 * ["codec seam"] + ["kernels"] + 2 * ["served request"])
    # the cell reports op_rate and not payload_rate: the bytes an operation carries
    # depend on which of the 16 sizes a seed drew for its pool (PERF.md, PR 31), so
    # every metric that lists the cell moves op_rate
    rates = {m["name"]: m["workloads"] for m in bench["end_to_end"] if "workloads" in m}
    assert "mixed-randsize" in rates["op_rate"] and "mixed-randsize" not in rates["payload_rate"]
    for m in bench["per_layer"]:
        if "mixed-randsize" in m["workloads"]:
            assert m["moves"] == "op_rate", m["name"]
    for name in ("gen_busy", "put_tail", "get_tail", "stat_p50", "delete_p50", "meta_round",
                 "put_body", "handler_queue_wait", "gil_late"):
        assert "mixed-randsize" in layers[name]["workloads"], name


def test_the_traffic_file_is_the_issues_letter_for_letter():
    assert SIZES == [round(40960 * 256 ** ((j + 0.5) / 16)) for j in range(16)]
    assert {w for _, w in TRAFFIC["sizes"]} == {1}
    assert TRAFFIC == dict(TRAFFIC, loop="closed", clients=20, pool_objects=256, lost_drives=[],
                           mix={"GET": 45, "STAT": 30, "PUT": 15, "DELETE": 10})
    assert sum(SIZES) / 16 == 1874188.75
    shards = [-(-s // 8) for s in SIZES]
    assert all(n % 16384 and s % 32 for n, s in zip(shards, SIZES))
    assert (min(shards), max(shards)) == (6089, 1102180)
