"""The readers that account a request's wall and the server's CPU
(``ledger_readers.py``) on a canned ``kernel-stats`` pair: the window's delta and
the division, every metric file through the harness's own loader, None from a
program without the tables (the parent commit), and the entries of
``BENCHMARK.json`` by name - wherever in the list later PRs leave them."""
import copy
import json
import os
import types

import ledger_readers as L
import pytest
from conftest import BENCH, REPO


def verb(name, count, wall, cpu, queue, **own):
    return {"verb": name, "count": count, "wall_seconds": wall, "cpu_seconds": cpu,
            "queue_wait_seconds": queue, "self": {k: list(v) for k, v in own.items()}}


def phase(count, wall, queue, run):
    return {"count": count, "wall_seconds": wall, "last_queue_seconds": queue,
            "last_run_seconds": run}


def s3_request(count):
    return [{"role": "handler", "name": "s3_request", "count": count, "wall_seconds": 1.0,
             "cpu_seconds": 0.1},
            {"role": "other", "name": "s3_request", "count": 1, "wall_seconds": 0.1,
             "cpu_seconds": 0.01}]


# the window: 10 PUTs, 30 GETs, 20 HEADs and one admin call = 61 requests; every verb's
# self adds up to its wall on both sides
OPEN = {
    "spans": s3_request(100),
    "requests": [
        verb("PutObject", 5, 3.0, 0.25, 0.3, s3_request=(5, 0.5), ol_put_object=(5, 1.0),
             stream_disk=(10, 0.7), put_close_wait=(5, 0.4), put_rename_wait=(5, 0.4)),
        verb("GetObject", 50, 10.0, 1.0, 2.0, s3_request=(50, 2.0), ol_get_object=(50, 3.0),
             stream_disk=(50, 5.0)),
        verb("other", 2, 0.02, 0.01, 0.0, s3_request=(2, 0.02)),
    ],
    "fanout": {"put_flush": phase(10, 0.7, 0.2, 0.3), "put_close": phase(5, 0.4, 0.1, 0.2),
               "put_rename": phase(5, 0.4, 0.1, 0.3), "get_reads": phase(50, 5.0, 3.0, 1.0)},
    "cpu": {"batcher": 1.0, "handler": 10.0, "iopool": 4.0, "loop": 3.0, "native": 2.0,
            "other": 5.0, "process_seconds": 25.5},
    "loops": [{"loop": 0, "requests": 40, "queue_wait_seconds": 1.0},
              {"loop": 1, "requests": 30, "queue_wait_seconds": 0.5},
              {"loop": 2, "requests": 30, "queue_wait_seconds": 0.5}],
}
CLOSE = {
    "spans": s3_request(161),
    "requests": [
        verb("PutObject", 15, 10.0, 0.75, 1.0, s3_request=(15, 1.0), ol_put_object=(15, 2.5),
             stream_disk=(30, 2.7), put_close_wait=(15, 1.4), put_rename_wait=(15, 2.4)),
        verb("GetObject", 80, 19.0, 1.6, 3.0, s3_request=(80, 3.0), ol_get_object=(80, 5.0),
             stream_disk=(80, 11.0)),
        # a verb the window's first snapshot had not seen yet
        verb("HeadObject", 20, 1.0, 0.1, 0.5, s3_request=(20, 0.2),
             ol_get_object_info=(20, 0.3), meta_read_all=(20, 0.5)),
        verb("other", 3, 0.03, 0.02, 0.0, s3_request=(3, 0.03)),
    ],
    "fanout": {"put_flush": phase(30, 2.7, 0.8, 1.3), "put_close": phase(15, 1.4, 0.2, 1.1),
               "put_rename": phase(15, 2.4, 0.4, 1.8), "get_reads": phase(80, 11.0, 6.0, 4.0)},
    "cpu": {"batcher": 1.122, "handler": 11.525, "iopool": 4.61, "loop": 3.305,
            "native": 2.061, "other": 5.0, "warmer": 0.2, "process_seconds": 28.55},
    "loops": [{"loop": 0, "requests": 70, "queue_wait_seconds": 3.0},
              {"loop": 1, "requests": 45, "queue_wait_seconds": 0.7},
              {"loop": 2, "requests": 46, "queue_wait_seconds": 0.7},
              {"loop": 3, "requests": 0, "queue_wait_seconds": 0.0}],
}
WANT = {
    "request_cpu": 1e3 * (0.5 + 0.6 + 0.1 + 0.01) / 61,
    # the root's and the ol_* spans' self over the wall: PUT 0.5 + 1.5 of 7, GET 1 + 2 of 9,
    # HEAD 0.2 + 0.3 of 1, other 0.01 of 0.01
    "unspanned_share": 100 * (2.0 + 3.0 + 0.5 + 0.01) / (7.0 + 9.0 + 1.0 + 0.01),
    "put_drive_wait": 1e3 * (2.0 + 1.0 + 2.0) / 10,
    "put_straggler_queue": 1e3 * (0.6 + 0.1 + 0.3) / 10,
    "get_drive_wait": 1e3 * 6.0 / 30,
    "server_cpu": 1e3 * 3.05 / 61,
    "cpu_handler": 1e3 * 1.525 / 61,
    "cpu_loop": 1e3 * 0.305 / 61,
    "cpu_iopool": 1e3 * 0.61 / 61,
    "cpu_batcher": 1e3 * 0.122 / 61,
    "loop_skew": 30 * 4 / 61,  # loop 0 served 30 of the 61, over four loops
}
ELEVEN = list(WANT)


def a_run(a, b):
    return types.SimpleNamespace(ks_open=a, ks_close=b)


def test_the_canned_verbs_add_up_as_the_programs_do():
    for ks in (OPEN, CLOSE):
        for r in ks["requests"]:
            assert sum(v[1] for v in r["self"].values()) == pytest.approx(r["wall_seconds"])


@pytest.mark.parametrize("name", ELEVEN)
def test_reader_is_the_windows_delta_and_its_metric_file_calls_it(name):
    import run as harness

    assert harness.read_metric(name, a_run(OPEN, CLOSE)) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", ELEVEN)
def test_reader_reads_none_on_a_parent_without_the_tables(name):
    """The parent commit keeps ``spans`` and ``probe`` and none of the four tables:
    every reader reads None there and may not raise, so both sides run the cells."""
    import run as harness

    def old(ks):
        return {k: v for k, v in ks.items() if k in ("spans", "probe")}

    for run in (a_run(old(OPEN), old(CLOSE)), a_run(None, None), a_run({}, {}),
                a_run(CLOSE, CLOSE)):  # nothing moved: no ratio
        assert harness.read_metric(name, run) is None


def test_a_role_no_thread_has_leaves_its_metric_out():
    close = copy.deepcopy(CLOSE)
    del close["cpu"]["batcher"]
    assert L.cpu_per_request(a_run(OPEN, close), "batcher") is None
    assert L.cpu_per_request(a_run(OPEN, close), "handler") == pytest.approx(WANT["cpu_handler"])
    # a role the first snapshot had not met counts from zero
    assert L.cpu_per_request(a_run(OPEN, CLOSE), "warmer") == pytest.approx(1e3 * 0.2 / 61)


def test_a_window_without_a_put_reads_no_put_metric_and_still_the_gets():
    close = copy.deepcopy(CLOSE)
    close["requests"][0] = copy.deepcopy(OPEN["requests"][0])
    run = a_run(OPEN, close)
    assert L.put_drive_wait(run) is None and L.put_straggler_queue(run) is None
    assert L.get_drive_wait(run) == pytest.approx(WANT["get_drive_wait"])


def test_loop_skew_is_one_when_even_and_the_loop_count_when_one_took_all():
    def loops(*requests):
        return {"loops": [{"loop": i, "requests": n, "queue_wait_seconds": 0.0}
                          for i, n in enumerate(requests)]}

    assert L.loop_skew(a_run(loops(0, 0, 0, 0), loops(5, 5, 5, 5))) == 1.0
    assert L.loop_skew(a_run(loops(3, 3, 3, 3), loops(3, 23, 3, 3))) == 4.0
    assert L.loop_skew(a_run(loops(1, 1), loops(1, 1))) is None


def test_benchmark_json_names_the_eleven_with_their_cells_and_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    layers = {m["name"]: m for m in bench["per_layer"]}  # by name, wherever they stand
    cells = [w["name"] for w in bench["workloads"]]
    reports = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    mixed = [c for c in cells if c != "get-degraded-10m"]
    for name in ELEVEN:
        m = layers[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["better"] == "lower" and m["source"] in ("program_span", "program_counter")
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py")), name
        # a cell lists a metric only if it reports the end-to-end metric it moves
        assert set(m["workloads"]) <= set(reports[m["moves"]]), name
        want = mixed if name.startswith("put_") else cells
        assert m["workloads"] == [c for c in want if c in reports[m["moves"]]], name
    assert {layers[n]["moves"] for n in ELEVEN} == {"op_rate", "payload_rate"}
    assert [n for n in ELEVEN if layers[n]["moves"] == "payload_rate"] == [
        "get_drive_wait", "cpu_batcher"]
    known = {m["layer"] for m in bench["per_layer"] if m["name"] not in ELEVEN}
    assert {layers[n]["layer"] for n in ELEVEN} - known == {"device, host side"}
