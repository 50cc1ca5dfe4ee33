"""Whole runs of the harness on the sandbox CPU (``--rehearse-cpu``: cut size,
XLA:CPU, every line says platform=cpu).  The control of each cell and the
altered answer have to come out as not correct; a sound run as correct; the
plain command has to refuse to run without a TPU.  About a minute each."""
import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, REPO

RUN = [sys.executable, os.path.join(BENCH, "run.py")]


def rehearse(workload, *extra, seed=77):
    p = subprocess.run(RUN + ["--workload", workload, "--seed", str(seed), "--seconds", "5",
                              "--rehearse-cpu", *extra],
                       capture_output=True, text=True, timeout=900, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    assert all("platform=cpu" in ln for ln in p.stderr.splitlines() if ln.startswith("platform")
               or "benchmark:" in ln)
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "platform=cpu" and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    return line


def failing(line):
    return {k for k, (v, limit) in line["compared"].items()
            if not (v >= 1 if limit == ">= 1" else v <= limit)}


def test_sound_run_is_correct_and_reports_every_metric():
    line = rehearse("mixed-10m", "--trace", "0")
    assert line["correct"] is True and not failing(line)
    assert set(line["metrics"]) == {"payload_rate", "op_rate", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_control_parity_never_computed():
    line = rehearse("mixed-10m", "--break", "parity_zero")
    assert line["correct"] is False and "decode_mismatch" in failing(line)


def test_control_lost_shards_not_rebuilt():
    line = rehearse("get-degraded-10m", "--break", "no_reconstruct")
    assert line["correct"] is False and "wrong_answers" in failing(line)


def test_answer_altered_where_it_is_produced():
    line = rehearse("mixed-10m", "--break", "flip_get")
    assert line["correct"] is False and "wrong_answers" in failing(line)


def test_plain_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(RUN + ["--workload", "mixed-10m", "--seed", "1", "--seconds", "1"],
                       capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    assert p.returncode == 3 and p.stdout == ""


def test_alone_in_a_directory_it_refuses(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = subprocess.run([sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload",
                        "mixed-10m", "--seed", "1", "--seconds", "1"],
                       capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert p.returncode == 2 and p.stdout == ""


def test_interpreted_kernels_are_refused():
    env = dict(os.environ, MINIO_TPU_CODEC_INTERPRET="1")
    p = subprocess.run(RUN + ["--workload", "mixed-10m", "--seed", "1", "--seconds", "1"],
                       capture_output=True, text=True, timeout=120, cwd=REPO, env=env)
    assert p.returncode == 2 and p.stdout == ""
