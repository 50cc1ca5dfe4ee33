"""The reduction from a trace to busy, idle and per-program time, on a trace
recorded on a TPU v5 lite (mixed-10m, 5 s slice; device planes whole, host
planes cut to the benchmark's own spans)."""
import gzip
import json
import os

import pytest
import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data", "mixed-10m.planes.json.gz")


@pytest.fixture(scope="module")
def planes():
    with gzip.open(DATA) as f:
        return json.load(f)


def test_recorded_trace(planes):
    r = T.reduce_planes(planes)
    assert r["window_s"] == pytest.approx(4.954886719)
    assert r["busy_s"] == pytest.approx(0.005794475)
    assert r["busy_s_by_device"] == [pytest.approx(0.005794475)]
    assert r["program_calls"] == {"jit_digest_words": 36, "jit_encode_words_fused1": 12,
                                  "jit_reconstruct_words_batch": 1}
    assert r["program_s"]["jit_encode_words_fused1"] == pytest.approx(0.004109504)
    # a program's module time covers its ops; the busy union cannot pass their sum
    assert r["busy_s"] <= sum(r["program_s"].values()) + 1e-9
    top, secs = r["device_ops"][0]
    assert top == "jit_encode_words_fused1/encode_hash_fused.1" and secs == pytest.approx(0.004101545)
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert all(" = " not in name and len(name) < 130 for name, _ in r["device_ops"])
    assert r["idle_gaps"][0][0] == "ol_put_object"


def test_idle_is_all_accounted_for(planes):
    dev = next(p for p in planes if p["name"] == "/device:TPU:0")
    ops = next(ln["events"] for ln in dev["lines"] if ln["name"] == "XLA Ops")
    busy = T._union([(s, s + d) for _, s, d in ops])
    every = [(s, s + d) for p in planes for ln in p["lines"] for _, s, d in ln["events"]]
    lo, hi = min(s for s, _ in every), max(e for _, e in every)
    spans = [(s, s + d, n) for p in planes if p is not dev for ln in p["lines"]
             for n, s, d in ln["events"]]
    by_span = T.attribute_idle(T._gaps(busy, lo, hi), spans)
    idle = (hi - lo - sum(b - a for a, b in busy)) / 1e9
    assert sum(by_span.values()) == pytest.approx(idle, rel=1e-6)


def test_attribute_idle_splits_between_open_spans():
    gaps = [(0, 100), (200, 300)]
    spans = [(0, 50, "a"), (0, 100, "b"), (250, 400, "a")]
    got = T.attribute_idle(gaps, spans)
    # 0-50: a and b share; 50-100: b alone; 200-250: nobody; 250-300: a
    assert got == pytest.approx({"a": (25 + 50) / 1e9, "b": (25 + 50) / 1e9,
                                 "_no_span_": 50 / 1e9})


def test_no_device_plane_reads_nothing():
    assert T.reduce_planes([{"name": "/host:CPU", "lines": [
        {"name": "t", "events": [("bm/x", 0, 10)]}]}]) == {}


def test_names():
    assert T.program_name("jit_digest_words(7070722450448031488)") == "jit_digest_words"
    assert T.op_name("%fusion.3 = (u32[8,128]{1,0}) fusion(u32[1,8]{0} %x), kind=kLoop") == "fusion.3"
