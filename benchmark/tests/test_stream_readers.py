"""The readers of the deployment whose objects are many blocks long
(``stream_readers.py``) on a recorded ``kernel-stats`` pair: the window's delta,
the division, None where the program keeps no such counter or span (the parent
commit); the configuration's file against the one it differs from, the traffic
file, and what ``BENCHMARK.json`` gained."""
import json
import os
import types

import pytest
import stream_readers as S
from conftest import BENCH, HERE, REPO

with open(os.path.join(HERE, "data", "stream.kernel-stats.json")) as f:
    RECORDED = json.load(f)
OPEN, CLOSE = RECORDED["open"], RECORDED["close"]
FOUR = ["stream_blocks", "readahead_wait", "get_first_write", "launch_peak"]
WANT = {
    # two PUTs and three GETs of 6.4 blocks, one PUT of one block: 36 blocks, 6 streams
    "stream_blocks": 6.0,
    "readahead_wait": 1e3 * 0.516386 / 6,  # a wait a batch, two batches a GET
    "get_first_write": 1e3 * (0.475005 - 0.208972) / 3,
    "launch_peak": 0.25,  # the tests' launch of 256 KiB
}


def a_run(a, b):
    return types.SimpleNamespace(ks_open=a, ks_close=b)


def without(ks: dict, *tables: str) -> dict:
    out = {k: v for k, v in ks.items() if k not in tables}
    out["spans"] = [r for r in ks["spans"]
                    if r["name"] not in ("stream_readahead_wait", "get_first_write")]
    return out


def test_the_recording_moved_every_counter_the_readers_read():
    a, b = OPEN["stream"], CLOSE["stream"]
    moved = {d: {f: b[d][f] - a[d][f] for f in b[d]} for d in b}
    assert moved == {"encode": {"streams": 3, "blocks": 15, "batches": 5, "tail_groups": 2},
                     "decode": {"streams": 3, "blocks": 21, "batches": 6, "tail_groups": 3}}
    la, lb = OPEN["launch"], CLOSE["launch"]
    assert lb["count"] - la["count"] == 31 and lb["split_calls"] - la["split_calls"] == 4
    assert sum(int(s) * n for s, n in lb["sizes"].items()) == lb["bytes"]
    assert max(int(s) for s in lb["sizes"]) == lb["max_bytes"] == 262144
    assert la["max_bytes"] == 131072  # since boot; the window's peak is the sizes' to tell


@pytest.mark.parametrize("name", FOUR)
def test_reader_is_the_windows_delta_and_its_metric_file_calls_it(name):
    import run as harness

    run = a_run(OPEN, CLOSE)
    assert getattr(S, name)(run) == pytest.approx(WANT[name], rel=1e-9)
    assert harness.read_metric(name, run) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", FOUR)
def test_reader_reads_none_from_a_program_without_the_counters(name):
    """The parent commit keeps neither table nor span: every reader reads None
    there and may not raise, so the parent runs the cell."""
    old = a_run(without(OPEN, "stream", "launch"), without(CLOSE, "stream", "launch"))
    assert getattr(S, name)(old) is None
    assert getattr(S, name)(a_run(None, None)) is None
    assert getattr(S, name)(a_run({}, {})) is None
    assert getattr(S, name)(a_run(CLOSE, CLOSE)) is None  # nothing moved: no ratio


def test_launch_peak_is_the_windows_and_not_since_boot():
    """A window in which only small launches were made reads their size, though
    ``max_bytes`` remembers a larger one from before."""
    later = json.loads(json.dumps(CLOSE))
    later["launch"]["sizes"]["32768"] += 2
    later["launch"]["count"] += 2
    assert S.launch_peak(a_run(CLOSE, later)) == 32768 / (1 << 20)


def test_the_configuration_is_the_defaults_under_64_mib_objects():
    with open(os.path.join(BENCH, "configs", "ec8p4-12d-64m.json")) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "configs", "ec8p4-12d-defaults.json")) as f:
        base = json.load(f)
    assert "env" not in conf and "env" not in base
    for key in ("nodes", "sets", "drives_per_set", "chips", "placement", "erasure",
                "server_args", "guarantees", "compared"):
        assert conf[key] == base[key], key
    assert set(conf) - set(base) == {"differs_from_ec8p4-12d-defaults", "object_sizes"}
    assert set(conf["reduced"]) == {"pool_objects", "clients"}
    assert {"disable_multipart_default", "perf_object_size_default"} <= set(conf["assumed"])
    size, block, k = conf["object_sizes"]["bytes"], conf["erasure"]["block_size"], 8
    assert size == 64 << 20 and divmod(size, block) == (6, 4 << 20)
    assert 6 * (32 + block // k) + 32 + (4 << 20) // k == 8388832  # a shard file
    with open(os.path.join(BENCH, "traffic", "mixed-64m.json")) as f:
        traffic = json.load(f)
    assert traffic["sizes"] == [[size, 1]] and traffic["lost_drives"] == []
    assert (traffic["loop"], traffic["clients"], traffic["pool_objects"]) == ("closed", 8, 40)
    assert traffic["mix"] == {"GET": 45, "STAT": 30, "PUT": 15, "DELETE": 10}
    # byte for byte the pool of the 10 MiB cells
    assert traffic["pool_objects"] * size == 256 * block


def test_benchmark_json_gained_one_config_one_cell_and_four_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = next(c for c in bench["configs"] if c["name"] == "ec8p4-12d-64m")
    assert conf["file"] == "benchmark/configs/ec8p4-12d-64m.json"
    assert conf["reduced"] == ["pool_objects", "clients"] and len(conf["source"]) <= 200
    cell = next(w for w in bench["workloads"] if w["name"] == "mixed-64m")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ec8p4-12d-64m", "mixed-64m", 1)
    assert not any(w["chips"] == 4 for w in bench["workloads"])
    rates = {m["name"]: m for m in bench["end_to_end"]}
    assert "mixed-64m" in rates["payload_rate"]["workloads"]
    assert "mixed-64m" in rates["op_rate"]["workloads"] and "workloads" not in rates["setup_s"]
    layers = {m["name"]: m for m in bench["per_layer"]}
    assert [layers[n]["workloads"] for n in FOUR] == [
        ["mixed-64m", "mixed-10m"], ["mixed-64m"], ["mixed-64m", "mixed-10m-defaults"],
        ["mixed-64m", "mixed-10m"]]
    assert [layers[n]["moves"] for n in FOUR] == ["payload_rate"] * 2 + ["op_rate", "payload_rate"]
    # everything the defaults cell reads, the new cell reads; and the control of the widths
    for m in bench["per_layer"]:
        if "mixed-10m-defaults" in m.get("workloads", []) or m["name"] == "pad_ratio":
            assert "mixed-64m" in m["workloads"], m["name"]
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py")), m["name"]
