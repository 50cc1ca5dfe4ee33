"""The readers of the defaults deployment (``defaults_readers.py``): each of the
five on a recorded ``kernel-stats`` pair - the window's delta, the division, 0 for
a count that did not move, None where the program has no such counter - the cost
function behind the roofline share, and the configuration's file against the one
it differs from."""
import json
import os
import types

import defaults_readers as D
import pytest
import roofline
import roofline_defaults
from conftest import BENCH, HERE, REPO

with open(os.path.join(HERE, "data", "defaults.kernel-stats.json")) as f:
    RECORDED = json.load(f)
OPEN, CLOSE = RECORDED["open"], RECORDED["close"]
FIVE = ["hedged_read_share", "healthy_reconstruct_share", "loss_patterns", "matrix_time",
        "hedge_reconstruct_roofline"]
# a trace as trace_reduce.py hands it over: device seconds by program
TRACE = {"program_s": {"jit_reconstruct_words_batch(123)": 2e-6, "jit_digest_words(9)": 1.0},
         "busy_s": 1.0, "window_s": 5.0}
CONFIG = {"erasure": {"data": 3, "parity": 3}}


def a_run(a, b, trace=TRACE):
    return types.SimpleNamespace(ks_open=a, ks_close=b, ks_trace_open=a, ks_trace_close=b,
                                 trace=trace, config=CONFIG, traffic={"lost_drives": []},
                                 device={"kind": "TPU v5 lite"}, t0=100.0, t1=145.0)


def want() -> dict:
    h0, h1, r0, r1 = OPEN["hedge"], CLOSE["hedge"], OPEN["reconstruct"], CLOSE["reconstruct"]
    gets = [next(r["streams"] for r in ks["streams"] if r["kind"] == "decode")
            for ks in (OPEN, CLOSE)]
    span = [next(r for r in ks["spans"] if r["name"] == "seam_matrix") for ks in (OPEN, CLOSE)]
    counted = [next(r["bytes"] for r in ks["ops"] if r["op"] == "reconstruct")
               for ks in (OPEN, CLOSE)]
    rebuilt = r1["bytes_rebuilt"] - r0["bytes_rebuilt"]
    nbytes = (counted[1] - counted[0]) * 3 / 6 + rebuilt
    least = max(nbytes / 819e9, 2.0 * 3 * rebuilt / 393e12)
    return {
        "hedged_read_share": 100.0 * (h1["launched"] - h0["launched"])
        / (h1["shard_reads"] - h0["shard_reads"]),
        "healthy_reconstruct_share": 100.0 * (r1["healthy_calls"] - r0["healthy_calls"])
        / (gets[1] - gets[0]),
        "loss_patterns": float(r1["patterns_seen"] - r0["patterns_seen"]),
        "matrix_time": 1e3 * (span[1]["wall_seconds"] - span[0]["wall_seconds"])
        / (span[1]["count"] - span[0]["count"]),
        "hedge_reconstruct_roofline": 100.0 * least / 2e-6,
    }


def test_the_recording_moved_every_counter_the_readers_read():
    assert CLOSE["hedge"]["launched"] > OPEN["hedge"]["launched"] > 0
    assert CLOSE["reconstruct"]["patterns_seen"] > OPEN["reconstruct"]["patterns_seen"]
    assert CLOSE["reconstruct"]["matrix_cache"]["miss"] == CLOSE["reconstruct"]["patterns_seen"]
    assert CLOSE["breaker"] == {"error": 0, "outlier": CLOSE["breaker"]["outlier"]}


@pytest.mark.parametrize("name", FIVE)
def test_reader_is_the_windows_delta(name):
    got = getattr(D, name)(a_run(OPEN, CLOSE))
    assert got == pytest.approx(want()[name], rel=1e-9)
    assert 0 <= got < (100 if name.endswith(("_share", "_roofline")) else 1e6)


@pytest.mark.parametrize("name", FIVE)
def test_metric_file_calls_its_reader(name):
    import run as harness

    assert harness.read_metric(name, a_run(OPEN, CLOSE)) == pytest.approx(want()[name])


@pytest.mark.parametrize("name", FIVE)
def test_reader_reads_none_from_a_program_without_the_counters(name):
    """The parent commit keeps no ``reconstruct`` table, no ``shard_reads`` and
    no ``seam_matrix`` span: the reader may not raise there."""
    old = {"hedge": {"launched": 3, "won": 1, "wasted": 2}, "streams": OPEN["streams"],
           "ops": OPEN["ops"], "spans": [r for r in OPEN["spans"] if r["name"] != "seam_matrix"]}
    newer = dict(old, hedge={"launched": 9, "won": 4, "wasted": 5}, streams=CLOSE["streams"],
                 ops=CLOSE["ops"])
    assert getattr(D, name)(a_run(old, newer)) is None
    assert getattr(D, name)(a_run(None, None)) is None


def test_a_window_in_which_nothing_moved():
    run = a_run(CLOSE, CLOSE)
    assert D.loss_patterns(run) == 0.0  # a count: no new pattern is a reading
    assert D.matrix_time(run) == 0.0  # no look-up: no time spent in one
    for name in ("hedged_read_share", "healthy_reconstruct_share",
                 "hedge_reconstruct_roofline"):  # a share of nothing is not
        assert getattr(D, name)(run) is None
    assert D.hedge_reconstruct_roofline(a_run(OPEN, CLOSE, trace=None)) is None
    assert D.hedge_reconstruct_roofline(a_run(OPEN, CLOSE, trace={"program_s": {}})) is None


def test_the_cost_counts_k_rows_read_and_the_rebuilt_rows_written():
    row = 1310720  # a 10 MiB block at EC 8+4
    nbytes, nops = roofline_defaults.hedged_reconstruct_cost(12 * row, 1 * row, 8, 4)
    assert nbytes == 9 * row and nops == 2.0 * 8 * row
    # what the kernel moves is n rows in and k out: the share cannot pass 100 %
    assert nbytes < (12 + 8) * row
    assert roofline.least_seconds("TPU v5 lite", nbytes, nops) == nbytes / 819e9


def test_the_configuration_differs_from_its_control_in_env_only():
    with open(os.path.join(BENCH, "configs", "ec8p4-12d.json")) as f:
        control = json.load(f)
    with open(os.path.join(BENCH, "configs", "ec8p4-12d-defaults.json")) as f:
        config = json.load(f)
    assert "env" not in config and "env_why" not in config
    assert set(control["env"]) == {"MINIO_TPU_HEDGE", "MINIO_TPU_BREAKER_OUTLIER"}
    told_apart = {"name", "source", "source_part", "differs_from_ec8p4-12d"}
    for key in set(control) - {"env", "env_why"} - told_apart:
        assert config[key] == control[key], key
    assert set(config) - set(control) == {"differs_from_ec8p4-12d"}
    assert config["name"] == "ec8p4-12d-defaults" and config["source"] != control["source"]


def test_benchmark_json_has_the_cell_and_four_of_the_five():
    """``hedge_reconstruct_roofline`` is read by no cell: a traced slice of 5 s
    often holds no reconstruct of a healthy set (PERF.md, PR 27)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][-1]
    assert cell == dict(cell, name="mixed-10m-defaults", config="ec8p4-12d-defaults",
                        traffic="mixed-10m", chips=1)
    conf = bench["configs"][-1]
    assert conf["file"] == "benchmark/configs/ec8p4-12d-defaults.json"
    with open(os.path.join(REPO, conf["file"])) as f:
        assert json.load(f)["source"] == conf["source"]
    tail = bench["per_layer"][-4:]
    assert [m["name"] for m in tail] == FIVE[:4]
    for m in tail:
        assert m["moves"] == "payload_rate" and m["workloads"][0] == "mixed-10m-defaults"
    also_degraded = {m["name"] for m in tail if "get-degraded-10m" in m["workloads"]}
    assert also_degraded == {"loss_patterns", "matrix_time"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "mixed-10m" in m["workloads"]:
            assert "mixed-10m-defaults" in m["workloads"], m["name"]
