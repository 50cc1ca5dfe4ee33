"""Tier-1 gate for minio_tpu.analysis (ISSUE 2).

Three layers of coverage:

* the tree itself is clean — ``run_lint``/``run_contracts``/``run_locks``
  return no findings, which is the same check the CLI exit status
  encodes;
* every rule has a good/bad fixture pair under tests/data/analysis/,
  and the bad fixtures assert EXACT (rule, line) sets derived from the
  ``# VIOLATION: MTPU###`` markers in the fixture source;
* the kernel-contract registry covers 100% of the jitted entry points
  in minio_tpu/ops/ (introspection vs registry, so a new kernel without
  a contract fails here).
"""

import json
import os
import re
import subprocess
import sys
import time

import pytest

from minio_tpu import analysis
from minio_tpu.analysis import abi_contracts, kernel_contracts
from minio_tpu.analysis.findings import (
    RULES,
    Finding,
    filter_suppressed,
    noqa_codes_for_line,
    unused_suppressions,
)
from minio_tpu.analysis.hotpath_lint import lint_source
from minio_tpu.analysis.lockorder import (
    LockOrderAuditor,
    _ThreadingProxy,
)

FIXTURES = os.path.join(analysis.REPO_ROOT, "tests", "data", "analysis")
# fixtures are .py (# comments) or .cc (// comments)
_MARKER_RE = re.compile(r"(?:#|//)\s*VIOLATION:\s*(MTPU\d{3})")


def _fixture_lines(name):
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as fh:
        return fh.read().splitlines()


def _lint_fixture(name, *, rel_path=None):
    """Lint one fixture file, noqa-filtered, as the CLI would."""
    lines = _fixture_lines(name)
    rel = rel_path or f"tests/data/analysis/{name}"
    found = lint_source(rel, "\n".join(lines) + "\n")
    return filter_suppressed(found, {rel: lines})


def _lint_fixture_with_106(name):
    """Lint + unused-suppression audit, exactly as run_lint composes."""
    lines = _fixture_lines(name)
    rel = f"tests/data/analysis/{name}"
    text = "\n".join(lines) + "\n"
    raw = lint_source(rel, text)
    found = raw + unused_suppressions(rel, text, raw)
    return filter_suppressed(found, {rel: lines})


def _abi_fixture(py_name, cc_name=None):
    """ABI-check one fixture pair, noqa-filtered on the Python side."""
    py_lines = _fixture_lines(py_name)
    py_rel = f"tests/data/analysis/{py_name}"
    cc_text = cc_rel = None
    if cc_name is not None:
        cc_text = "\n".join(_fixture_lines(cc_name)) + "\n"
        cc_rel = f"tests/data/analysis/{cc_name}"
    found = abi_contracts.analyze(
        "\n".join(py_lines) + "\n", py_rel, cc_text, cc_rel
    )
    return filter_suppressed(found, {py_rel: py_lines})


def _expected_markers(name):
    """The (rule, line) set declared by # VIOLATION: markers."""
    out = set()
    for i, line in enumerate(_fixture_lines(name), start=1):
        for m in _MARKER_RE.finditer(line):
            out.add((m.group(1), i))
    return out


# -- the tree is clean --------------------------------------------------


def test_tree_lint_clean():
    """minio_tpu/ carries zero unsuppressed lint findings."""
    found = analysis.run_lint()
    assert found == [], "\n".join(f.render() for f in found)


def test_lock_builtin_scenario_clean():
    found = analysis.run_locks()
    assert found == [], "\n".join(f.render() for f in found)


def test_tree_abi_clean():
    """Every native export is bound, every binding matches, no buffer
    reaches the FFI seam unchecked."""
    found = analysis.run_abi()
    assert found == [], "\n".join(f.render() for f in found)


@pytest.fixture(scope="module")
def contract_findings():
    """Contracts traced once per module (eval_shape over the grid)."""
    return analysis.run_contracts()


def test_tree_contracts_clean(contract_findings):
    assert contract_findings == [], "\n".join(
        f.render() for f in contract_findings
    )


# -- contract registry covers every jitted entry point ------------------

# the entry points the tree ships, now registered in kernel_contracts
# (the deviceflow pass reads the same table); introspection must find
# at LEAST these (a rename or deletion shows up as a diff here, a new
# kernel shows up as MTPU204 in the contract run).
KNOWN_ENTRY_POINTS = kernel_contracts.KNOWN_ENTRY_POINTS


def test_introspection_finds_the_known_entry_points():
    eps = set(kernel_contracts.jit_entry_points())
    assert eps >= KNOWN_ENTRY_POINTS
    # hash.py intentionally exposes no module-level jitted functions,
    # and codec/backend.py routes through codec_step's kernels - but
    # both are WATCHED, so a jitted wrapper landing there without a
    # contract fails MTPU204 instead of dodging coverage
    assert not any(mod == "hash" for mod, _ in eps)
    assert "backend" in kernel_contracts._ops_modules()
    assert not any(mod == "backend" for mod, _ in eps)


def test_contract_registry_covers_all_entry_points(contract_findings):
    """100% coverage: registry == introspection, and the run agrees."""
    eps = set(kernel_contracts.jit_entry_points())
    covered = kernel_contracts.covered_entry_points()
    assert covered >= eps, f"uncovered: {sorted(eps - covered)}"
    assert [f for f in contract_findings if f.rule == "MTPU204"] == []


# -- fixture pairs: exact rule IDs and line numbers ---------------------

BAD_FIXTURES = [
    "bad_mtpu101.py",
    "bad_mtpu102.py",
    "bad_mtpu103.py",
    "bad_mtpu104.py",
    "bad_mtpu105.py",
]
GOOD_FIXTURES = [
    "good_mtpu101.py",
    "good_mtpu102.py",
    "good_mtpu103.py",
    "good_mtpu104.py",
    "good_mtpu105.py",
]


@pytest.mark.parametrize("name", BAD_FIXTURES)
def test_bad_fixture_exact_findings(name):
    expected = _expected_markers(name)
    assert expected, f"{name} declares no VIOLATION markers"
    got = {(f.rule, f.line) for f in _lint_fixture(name)}
    assert got == expected


@pytest.mark.parametrize("name", GOOD_FIXTURES)
def test_good_fixture_clean(name):
    found = _lint_fixture(name)
    assert found == [], "\n".join(f.render() for f in found)


# -- MTPU107: parity readback is scoped to ops/ + codec/backend.py ------
#
# The fixtures are linted under an ops/ rel_path (the scope is path-
# keyed, and tests/data/ is outside it), so they get their own tests
# instead of riding the BAD_FIXTURES/GOOD_FIXTURES param lists.


def test_bad_mtpu107_exact_findings_under_parity_scope():
    expected = _expected_markers("bad_mtpu107.py")
    assert expected, "bad_mtpu107.py declares no VIOLATION markers"
    got = {
        (f.rule, f.line)
        for f in _lint_fixture(
            "bad_mtpu107.py", rel_path="minio_tpu/ops/bad_mtpu107.py"
        )
    }
    assert got == expected


def test_good_mtpu107_clean_under_parity_scope():
    found = _lint_fixture(
        "good_mtpu107.py", rel_path="minio_tpu/ops/good_mtpu107.py"
    )
    assert found == [], "\n".join(f.render() for f in found)


def test_mtpu107_applies_to_codec_backend_file():
    found = _lint_fixture(
        "bad_mtpu107.py", rel_path="minio_tpu/codec/backend.py"
    )
    rules = {(f.rule, f.line) for f in found}
    # the np.asarray/np.array sites fire under the backend scope too;
    # line numbers match the ops-scope markers
    assert {
        (r, ln)
        for r, ln in _expected_markers("bad_mtpu107.py")
        if r == "MTPU107"
    } <= rules


def test_mtpu107_silent_outside_parity_scope():
    """The same source linted under server/ raises no MTPU107."""
    found = _lint_fixture(
        "bad_mtpu107.py", rel_path="minio_tpu/server/bad_mtpu107.py"
    )
    assert not any(f.rule == "MTPU107" for f in found), "\n".join(
        f.render() for f in found
    )


def test_bad_mtpu107_fused_seam_exact_findings():
    """The one-kernel (fused1) seam: parity plane AND its prefix-packed
    twin stay device-resident; eager readback of either outside the
    begin/end/drain seams fires MTPU107."""
    expected = _expected_markers("bad_mtpu107_fused.py")
    assert expected, "bad_mtpu107_fused.py declares no VIOLATION markers"
    got = {
        (f.rule, f.line)
        for f in _lint_fixture(
            "bad_mtpu107_fused.py",
            rel_path="minio_tpu/ops/bad_mtpu107_fused.py",
        )
    }
    assert got == expected


def test_good_mtpu107_fused_seam_clean():
    """Digest-only eager output at the fused1 begin seam plus parity /
    packed materialization inside *_end / drain lint clean."""
    found = _lint_fixture(
        "good_mtpu107_fused.py",
        rel_path="minio_tpu/ops/good_mtpu107_fused.py",
    )
    assert found == [], "\n".join(f.render() for f in found)


def test_mtpu107_fused_seam_applies_to_codec_backend_file():
    found = _lint_fixture(
        "bad_mtpu107_fused.py", rel_path="minio_tpu/codec/backend.py"
    )
    rules = {(f.rule, f.line) for f in found}
    assert {
        (r, ln)
        for r, ln in _expected_markers("bad_mtpu107_fused.py")
        if r == "MTPU107"
    } <= rules


# -- MTPU108: event-loop-blocking lint is scoped to server/ -------------
#
# Like MTPU107, the scope is path-keyed (async defs under
# minio_tpu/server/), so the fixtures are linted under a server/
# rel_path instead of riding the shared param lists.


def test_bad_mtpu108_exact_findings_under_server_scope():
    expected = _expected_markers("bad_mtpu108.py")
    assert expected, "bad_mtpu108.py declares no VIOLATION markers"
    got = {
        (f.rule, f.line)
        for f in _lint_fixture(
            "bad_mtpu108.py", rel_path="minio_tpu/server/bad_mtpu108.py"
        )
    }
    assert got == expected


def test_good_mtpu108_clean_under_server_scope():
    found = _lint_fixture(
        "good_mtpu108.py", rel_path="minio_tpu/server/good_mtpu108.py"
    )
    assert found == [], "\n".join(f.render() for f in found)


def test_mtpu108_silent_outside_server_scope():
    """The same source linted under codec/ raises no MTPU108 (the rule
    keys on the request plane, not on async syntax in general)."""
    found = _lint_fixture(
        "bad_mtpu108.py", rel_path="minio_tpu/codec/bad_mtpu108.py"
    )
    assert not any(f.rule == "MTPU108" for f in found), "\n".join(
        f.render() for f in found
    )


def test_mtpu108_fires_on_the_shipped_aio_module_if_seeded():
    """Canary: injecting a time.sleep into an async def of the real
    server/aio.py source is caught by the gate."""
    import os as _os

    aio_path = _os.path.join(
        analysis.REPO_ROOT, "minio_tpu", "server", "aio.py"
    )
    with open(aio_path, encoding="utf-8") as fh:
        src = fh.read()
    seeded = src.replace(
        "    async def _serve_conn(",
        "    async def _seeded(self):\n"
        "        time.sleep(1)\n\n"
        "    async def _serve_conn(",
        1,
    )
    assert seeded != src
    found = lint_source("minio_tpu/server/aio.py", seeded)
    assert any(f.rule == "MTPU108" for f in found)


# -- MTPU109: PartitionSpec literals live only in parallel/rules.py -----
#
# Scope is path-keyed (minio_tpu/parallel/ + minio_tpu/ops/, with
# parallel/rules.py itself exempt as the single source of truth), so
# the fixtures get dedicated tests like MTPU107/108.


def test_bad_mtpu109_exact_findings_under_parallel_scope():
    expected = _expected_markers("bad_mtpu109.py")
    assert expected, "bad_mtpu109.py declares no VIOLATION markers"
    got = {
        (f.rule, f.line)
        for f in _lint_fixture(
            "bad_mtpu109.py", rel_path="minio_tpu/parallel/bad_mtpu109.py"
        )
    }
    assert got == expected


def test_mtpu109_applies_under_ops_scope():
    got = {
        (f.rule, f.line)
        for f in _lint_fixture(
            "bad_mtpu109.py", rel_path="minio_tpu/ops/bad_mtpu109.py"
        )
    }
    assert {
        (r, ln)
        for r, ln in _expected_markers("bad_mtpu109.py")
        if r == "MTPU109"
    } <= got


def test_good_mtpu109_clean_under_parallel_scope():
    found = _lint_fixture(
        "good_mtpu109.py", rel_path="minio_tpu/parallel/good_mtpu109.py"
    )
    assert found == [], "\n".join(f.render() for f in found)


def test_mtpu109_exempts_the_rule_table_itself():
    """The same literals linted AS parallel/rules.py raise nothing —
    the table is where the literals are supposed to live."""
    found = _lint_fixture(
        "bad_mtpu109.py", rel_path="minio_tpu/parallel/rules.py"
    )
    assert not any(f.rule == "MTPU109" for f in found), "\n".join(
        f.render() for f in found
    )


def test_mtpu109_silent_outside_sharding_scope():
    found = _lint_fixture(
        "bad_mtpu109.py", rel_path="minio_tpu/server/bad_mtpu109.py"
    )
    assert not any(f.rule == "MTPU109" for f in found), "\n".join(
        f.render() for f in found
    )


# -- MTPU110: mutations flow through the cache-invalidation seam --------
#
# Scope is the two erasure object-layer files; each def is judged on
# its own body (lambdas attach to the enclosing def, nested defs do
# not), and delete_file on SYS_VOL (staging) is exempt.


def test_bad_mtpu110_exact_findings_under_objectlayer_scope():
    expected = _expected_markers("bad_mtpu110.py")
    assert expected, "bad_mtpu110.py declares no VIOLATION markers"
    got = {
        (f.rule, f.line)
        for f in _lint_fixture(
            "bad_mtpu110.py",
            rel_path="minio_tpu/objectlayer/erasure_object.py",
        )
    }
    assert got == expected


def test_mtpu110_applies_to_multipart_file():
    got = {
        (f.rule, f.line)
        for f in _lint_fixture(
            "bad_mtpu110.py",
            rel_path="minio_tpu/objectlayer/erasure_multipart.py",
        )
    }
    assert {
        (r, ln)
        for r, ln in _expected_markers("bad_mtpu110.py")
        if r == "MTPU110"
    } <= got


def test_good_mtpu110_clean_under_objectlayer_scope():
    found = _lint_fixture(
        "good_mtpu110.py",
        rel_path="minio_tpu/objectlayer/erasure_object.py",
    )
    assert found == [], "\n".join(f.render() for f in found)


def test_mtpu110_silent_outside_objectlayer_scope():
    """Other objectlayer files (xl_storage, disk cache, healing
    helpers) mutate via their own seams; the rule keys on the two
    erasure entry-point files only."""
    for rel in (
        "minio_tpu/objectlayer/xl_storage.py",
        "minio_tpu/storage/bad_mtpu110.py",
    ):
        found = _lint_fixture("bad_mtpu110.py", rel_path=rel)
        assert not any(f.rule == "MTPU110" for f in found), "\n".join(
            f.render() for f in found
        )


def test_mtpu110_in_rule_catalog():
    assert "MTPU110" in RULES


# -- MTPU111: S3-Select D2H only through the result-drain seam ----------
#
# Scope is the single file s3select/device.py (exact match, not a
# prefix), so the fixtures are linted AS that file; the seam is any
# enclosing function whose name contains "drain".


def test_bad_mtpu111_exact_findings_under_select_scope():
    expected = _expected_markers("bad_mtpu111.py")
    assert expected, "bad_mtpu111.py declares no VIOLATION markers"
    got = {
        (f.rule, f.line)
        for f in _lint_fixture(
            "bad_mtpu111.py", rel_path="minio_tpu/s3select/device.py"
        )
    }
    assert got == expected


def test_good_mtpu111_clean_under_select_scope():
    found = _lint_fixture(
        "good_mtpu111.py", rel_path="minio_tpu/s3select/device.py"
    )
    assert found == [], "\n".join(f.render() for f in found)


def test_mtpu111_silent_outside_select_scope():
    """The same source under another s3select module raises nothing —
    the drain seam is a device.py contract, not a package-wide one."""
    for rel in (
        "minio_tpu/s3select/vector.py",
        "minio_tpu/server/select.py",
    ):
        found = _lint_fixture("bad_mtpu111.py", rel_path=rel)
        assert not any(f.rule == "MTPU111" for f in found), "\n".join(
            f.render() for f in found
        )


def test_mtpu111_in_rule_catalog():
    assert "MTPU111" in RULES


def test_noqa_suppresses_matching_rule():
    found = _lint_fixture("noqa_suppressed.py")
    assert found == [], "\n".join(f.render() for f in found)


def test_noqa_for_other_rule_does_not_suppress():
    expected = _expected_markers("noqa_wrong_code.py")
    got = {(f.rule, f.line) for f in _lint_fixture("noqa_wrong_code.py")}
    assert got == expected


def test_noqa_parsing():
    assert noqa_codes_for_line("x = 1") is None
    assert noqa_codes_for_line("x = 1  # noqa") == set()
    assert noqa_codes_for_line("x  # noqa: MTPU103") == {"MTPU103"}
    assert noqa_codes_for_line("x  # noqa: MTPU101, MTPU102") == {
        "MTPU101",
        "MTPU102",
    }
    # a reason string after the code list must not break parsing
    assert noqa_codes_for_line(
        "x  # noqa: MTPU103 - logging must never raise"
    ) == {"MTPU103"}


# -- MTPU106: unused suppressions ---------------------------------------


def test_stale_suppression_is_flagged():
    expected = _expected_markers("bad_mtpu106.py")
    got = {
        (f.rule, f.line) for f in _lint_fixture_with_106("bad_mtpu106.py")
    }
    assert got == expected == {("MTPU106", 7)}


def test_live_and_deliberate_suppressions_are_clean():
    found = _lint_fixture_with_106("good_mtpu106.py")
    assert found == [], "\n".join(f.render() for f in found)


def test_unused_suppression_ignores_foreign_and_bare_noqa():
    src = (
        "import os  # noqa: F401\n"
        "x = 1  # noqa\n"
        "y = os.sep  # noqa: MTPU104\n"
    )
    found = unused_suppressions("f.py", src, [])
    assert [(f.rule, f.line) for f in found] == [("MTPU106", 3)]


def test_unused_suppression_skips_docstring_mentions():
    src = '"""docs say use # noqa: MTPU103 to silence."""\nx = 1\n'
    assert unused_suppressions("f.py", src, []) == []


def test_run_lint_composes_the_suppression_audit():
    """run_lint feeds the ABI pass's raw findings into the audit: the
    noqa-free tree stays clean end to end (the stale trace.py
    suppression this PR pruned would fail here)."""
    found = [f for f in analysis.run_lint() if f.rule == "MTPU106"]
    assert found == [], "\n".join(f.render() for f in found)


# -- ABI contracts (MTPU401-405): fixture pairs -------------------------

ABI_BAD_FIXTURES = [
    ("abi_bad_mtpu401.py", "abi_good.cc"),
    ("abi_bad_mtpu402.py", "abi_good.cc"),
    ("abi_bad_mtpu403.py", "abi_bad_mtpu403.cc"),
    ("abi_bad_mtpu404.py", None),
    ("abi_bad_mtpu405.py", None),
]


def test_abi_good_pair_clean():
    found = _abi_fixture("abi_good.py", "abi_good.cc")
    assert found == [], "\n".join(f.render() for f in found)


@pytest.mark.parametrize("py_name,cc_name", ABI_BAD_FIXTURES)
def test_abi_bad_fixture_exact_findings(py_name, cc_name):
    expected = _expected_markers(py_name)
    expected |= {
        (rule, line)
        for rule, line in (
            _expected_markers(cc_name) if cc_name else set()
        )
    }
    assert expected, f"{py_name} declares no VIOLATION markers"
    got = {(f.rule, f.line) for f in _abi_fixture(py_name, cc_name)}
    assert got == expected


def test_seeded_argtypes_drift_fails_with_exactly_mtpu402():
    """The acceptance fixture: arity matches, types drift - the checker
    reports MTPU402 and nothing else."""
    found = _abi_fixture("abi_bad_mtpu402.py", "abi_good.cc")
    assert found, "drift fixture produced no findings"
    assert {f.rule for f in found} == {"MTPU402"}
    assert any("c_size_t" in f.message for f in found)


def test_abi_export_parser_reads_the_real_table():
    with open(
        os.path.join(analysis.REPO_ROOT, abi_contracts.CC_REL),
        encoding="utf-8",
    ) as fh:
        exports = abi_contracts.parse_exports(fh.read())
    assert set(exports) >= {
        "gf_matmul",
        "gf_mul_acc",
        "phash256_rows",
        "encode_and_hash",
        "reconstruct_batch",
        "reconstruct_and_verify",
        "gf_has_avx2",
    }
    # every real export must carry a @ctypes annotation - an
    # unannotated export only gets arity/presence checks
    for name, exp in exports.items():
        assert exp.annot_args is not None, f"{name} lacks @ctypes"
    assert exports["reconstruct_and_verify"].c_arity == 12


def test_abi_noqa_suppresses_on_the_python_side():
    src = (
        "import ctypes\n"
        "def f(buf):\n"
        "    lib = ctypes.CDLL('x.so')\n"
        "    lib.k(buf.ctypes.data_as(ctypes.c_void_p), 4)"
        "  # noqa: MTPU405\n"
    )
    found = abi_contracts.analyze(src, "f.py")
    assert [f.rule for f in found] == ["MTPU405"]
    assert (
        filter_suppressed(found, {"f.py": src.splitlines()}) == []
    )


# -- directory exclusions are centralized and honored -------------------


def test_iter_py_files_prunes_excluded_dirs(tmp_path, monkeypatch):
    for rel in (
        "pkg/ok.py",
        "pkg/__pycache__/junk.py",
        "native/build/gen.py",
        "pkg/sub/also_ok.py",
    ):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text("x = 1\n")
    monkeypatch.setattr(analysis, "REPO_ROOT", str(tmp_path))
    assert analysis.iter_py_files(["pkg", "native"]) == [
        "pkg/ok.py",
        "pkg/sub/also_ok.py",
    ]
    # explicitly passing an excluded directory yields nothing
    assert analysis.iter_py_files(["native/build"]) == []
    assert analysis.iter_py_files(["pkg/__pycache__"]) == []


def test_is_excluded_matches_path_components():
    assert analysis.is_excluded("native/build/gen.py")
    assert analysis.is_excluded("a/__pycache__/b.py")
    assert analysis.is_excluded("minio_tpu/analysis/findings.py")
    assert not analysis.is_excluded("minio_tpu/utils/native.py")
    # a FILE named build is not a directory exclusion
    assert not analysis.is_excluded("minio_tpu/build.py")


def test_device_module_rules_are_path_scoped():
    """The same sync outside jit is flagged only under ops//codec/."""
    src = "def helper(x):\n    return x.block_until_ready()\n"
    dev = lint_source("minio_tpu/ops/fixture.py", src)
    assert [(f.rule, f.line) for f in dev] == [("MTPU101", 2)]
    assert lint_source("minio_tpu/server/fixture.py", src) == []
    # host_* boundary functions are the sanctioned sync points
    host = "def host_fetch(x):\n    return x.block_until_ready()\n"
    assert lint_source("minio_tpu/ops/fixture.py", host) == []


def test_syntax_error_becomes_mtpu100():
    found = lint_source("minio_tpu/ops/broken.py", "def f(:\n")
    assert [f.rule for f in found] == ["MTPU100"]


def test_findings_are_stable_sorted_and_serializable():
    a = Finding("MTPU103", "b.py", 2, "m")
    b = Finding("MTPU101", "a.py", 9, "m")
    c = Finding("MTPU101", "a.py", 3, "m")
    ordered = sorted([a, b, c], key=Finding.sort_key)
    assert ordered == [c, b, a]
    d = a.to_dict()
    assert d == {
        "rule": "MTPU103",
        "path": "b.py",
        "line": 2,
        "message": "m",
    }
    assert a.render() == "b.py:2: MTPU103 m"
    assert a.rule in RULES


# -- lock-order auditor unit behaviour ----------------------------------


def test_lockorder_detects_ab_ba_cycle():
    aud = LockOrderAuditor()
    proxy = _ThreadingProxy(aud)
    a, b = proxy.Lock(), proxy.Lock()
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    rep = aud.report()
    assert [f.rule for f in rep] == ["MTPU301"]
    assert "lock-order cycle" in rep[0].message


def test_lockorder_consistent_order_is_clean():
    aud = LockOrderAuditor()
    proxy = _ThreadingProxy(aud)
    a, b = proxy.Lock(), proxy.Lock()
    for _ in range(3):
        with a:
            with b:
                pass
    assert aud.cycles() == []
    assert aud.report() == []
    # one direction was observed, as an edge, exactly once
    assert len(aud.edge_labels()) == 1


def test_lockorder_rlock_reentry_is_not_a_cycle():
    aud = LockOrderAuditor()
    proxy = _ThreadingProxy(aud)
    r = proxy.RLock()
    with r:
        with r:
            pass
    assert aud.cycles() == []
    assert aud.edge_labels() == []


def test_lockorder_flags_sleep_under_lock():
    aud = LockOrderAuditor()
    proxy = _ThreadingProxy(aud)
    lk = proxy.Lock()
    real_sleep = time.sleep
    with aud.installed():
        with lk:
            time.sleep(0)
    assert time.sleep is real_sleep, "uninstall must restore time.sleep"
    rep = aud.report()
    assert [f.rule for f in rep] == ["MTPU302"]
    assert "time.sleep" in rep[0].message


def test_lockorder_sleep_without_lock_is_clean():
    aud = LockOrderAuditor()
    with aud.installed():
        time.sleep(0)
    assert aud.report() == []


def test_lockorder_condition_wait_repushes_held_stack():
    aud = LockOrderAuditor()
    proxy = _ThreadingProxy(aud)
    cond = proxy.Condition()
    with cond:
        assert aud.held_count() == 1
        cond.wait(timeout=0.01)  # releases + re-acquires under audit
        assert aud.held_count() == 1
    assert aud.held_count() == 0


def test_lockorder_install_restores_module_globals():
    import threading as real_threading

    from minio_tpu.dsync import local_locker

    aud = LockOrderAuditor(targets=("minio_tpu.dsync.local_locker",))
    with aud.installed():
        assert local_locker.threading is not real_threading
    assert local_locker.threading is real_threading


# -- MTPU5xx: interprocedural device-dataflow ---------------------------
#
# The deviceflow pass runs on PARSED sources (same trees the shared AST
# cache serves), so fixtures and seeded canaries are analyzed in memory
# exactly as the CLI would analyze them on disk.  MTPU504's root scope
# is path-keyed (minio_tpu/server/), so its fixtures use rel_path
# overrides like the MTPU107/108 ones.

from minio_tpu.analysis import callgraph  # noqa: E402
from minio_tpu.analysis.astcache import CACHE, parse_source  # noqa: E402
from minio_tpu.analysis.deviceflow import analyze_sources  # noqa: E402

DEVICEFLOW_REL_OVERRIDE = {
    "bad_mtpu504.py": "minio_tpu/server/bad_mtpu504.py",
    "good_mtpu504.py": "minio_tpu/server/good_mtpu504.py",
}


def _deviceflow_fixture(name, *, rel_path=None):
    """Deviceflow-analyze one fixture, noqa-filtered as the CLI would."""
    lines = _fixture_lines(name)
    rel = rel_path or DEVICEFLOW_REL_OVERRIDE.get(
        name, f"tests/data/analysis/{name}"
    )
    text = "\n".join(lines) + "\n"
    rep = analyze_sources({rel: parse_source(rel, text)})
    return filter_suppressed(rep.findings, {rel: lines})


@pytest.mark.parametrize(
    "name",
    [f"bad_mtpu50{i}.py" for i in range(1, 6)]
    + ["bad_mtpu505_subchunk.py"],
)
def test_bad_deviceflow_fixture_exact_findings(name):
    expected = _expected_markers(name)
    assert expected, f"{name} declares no VIOLATION markers"
    got = {(f.rule, f.line) for f in _deviceflow_fixture(name)}
    assert got == expected


@pytest.mark.parametrize(
    "name",
    [f"good_mtpu50{i}.py" for i in range(1, 6)]
    + ["good_mtpu505_subchunk.py"],
)
def test_good_deviceflow_fixture_clean(name):
    found = _deviceflow_fixture(name)
    assert found == [], "\n".join(f.render() for f in found)


def test_tree_deviceflow_clean():
    """minio_tpu/ carries zero unsuppressed deviceflow findings."""
    found = analysis.run_deviceflow()
    assert found == [], "\n".join(f.render() for f in found)


def _read_tree_source(rel):
    with open(os.path.join(analysis.REPO_ROOT, rel), encoding="utf-8") as fh:
        return fh.read()


def test_mtpu501_fires_on_seeded_codec_step_canary():
    """Canary: a copy of the REAL ops/codec_step.py that re-reads a
    donated buffer is caught, with exact rule id and line — the same
    discipline as the MTPU108 aio.py canary."""
    rel = "minio_tpu/ops/codec_step.py"
    src = _read_tree_source(rel)
    injected = (
        "\n\ndef _canary_reuse(words, parity_shards, shard_len):\n"
        "    parity, digests = encode_words_fused1(\n"
        "        words, parity_shards, shard_len\n"
        "    )\n"
        "    return words.sum(), parity\n"
    )
    seeded = src + injected
    # the pristine copy is clean ...
    clean = analyze_sources({rel: parse_source(rel, src)}).findings
    assert [f for f in clean if f.rule == "MTPU501"] == []
    # ... the mutated copy fires exactly where the re-read happens
    found = analyze_sources({rel: parse_source(rel, seeded)}).findings
    expect_line = seeded.splitlines().index(
        "    return words.sum(), parity"
    ) + 1
    assert {(f.rule, f.line) for f in found if f.rule == "MTPU501"} == {
        ("MTPU501", expect_line)
    }


def test_mtpu502_fires_on_seeded_backend_canary():
    """Canary: a copy of the REAL codec/backend.py that drains parity
    outside the registered seams is caught, exact rule id and line."""
    rel = "minio_tpu/codec/backend.py"
    src = _read_tree_source(rel)
    injected = (
        "\n\ndef _canary_peek(words, parity_shards, shard_len):\n"
        "    parity_w, digests = codec_step.encode_words_fused1(\n"
        "        words, parity_shards, shard_len\n"
        "    )\n"
        "    return np.asarray(parity_w)\n"
    )
    seeded = src + injected
    clean = analyze_sources({rel: parse_source(rel, src)}).findings
    assert [f for f in clean if f.rule == "MTPU502"] == []
    found = analyze_sources({rel: parse_source(rel, seeded)}).findings
    expect_line = seeded.splitlines().index(
        "    return np.asarray(parity_w)"
    ) + 1
    assert {(f.rule, f.line) for f in found if f.rule == "MTPU502"} == {
        ("MTPU502", expect_line)
    }


# -- call-graph coverage: introspection-closed, like MTPU204 ------------


@pytest.fixture(scope="module")
def tree_graph():
    sources = CACHE.load(analysis.iter_py_files())
    return sources, callgraph.build(sources)


def test_callgraph_resolves_every_registered_entry_point(tree_graph):
    """Every jitted entry point in kernel_contracts.KNOWN_ENTRY_POINTS
    resolves to a def node in the call graph (registry vs graph, the
    same closure discipline the MTPU204 coverage test applies)."""
    _, graph = tree_graph
    missing = [
        (mod, name)
        for mod, name in sorted(kernel_contracts.KNOWN_ENTRY_POINTS)
        if graph.resolve_short(mod, name) is None
    ]
    assert missing == []


def test_callgraph_records_every_boundary_site(tree_graph):
    """Introspection-closed: every call in server/ and codec/erasure.py
    that the boundary classifier recognizes has a recorded boundary
    edge at its exact line — no submit/bridge site goes unrecorded."""
    import ast as _ast

    sources, graph = tree_graph
    recorded = {(e.rel_path, e.line) for e in graph.boundary_edges()}
    checked = 0
    for rel, mod in sources.items():
        if not (
            rel.startswith("minio_tpu/server/")
            or rel == "minio_tpu/codec/erasure.py"
        ):
            continue
        assert mod.tree is not None
        for node in _ast.walk(mod.tree):
            if isinstance(node, _ast.Call) and callgraph.boundary_kind(
                node
            ):
                assert (rel, node.lineno) in recorded, (
                    f"boundary site {rel}:{node.lineno} unrecorded"
                )
                checked += 1
    # the seed tree ships pool submits in erasure.py and both bridge
    # directions in server/aio.py; an empty walk means scope rot
    assert checked >= 10
    kinds = {e.boundary for e in graph.boundary_edges()}
    assert {"pool", "loop-bridge", "loop-call", "thread"} <= kinds


def test_callgraph_stats_shape(tree_graph):
    _, graph = tree_graph
    stats = graph.stats()
    assert set(stats) == {"nodes", "edges", "boundary_edges", "seconds"}
    assert stats["nodes"] > 1000
    assert stats["edges"] > stats["boundary_edges"] > 0


# -- --changed-only soundness: reverse-dependency closure ---------------


def test_reverse_closure_retriggers_caller_on_helper_edit():
    """Editing a CALLEE must re-trigger deviceflow on its callers: the
    helper below starts host-pure (caller clean), then is edited to
    return a device value (caller's np.asarray becomes an MTPU502).
    The reverse-dependency closure of {helper} must contain the caller,
    so --changed-only reports the caller's finding; naive per-file
    gating would silently skip it."""
    helper_rel = "minio_tpu/cache/df_helper.py"
    caller_rel = "minio_tpu/cache/df_caller.py"
    caller_src = (
        "import numpy as np\n"
        "from minio_tpu.cache.df_helper import make\n"
        "\n"
        "def use():\n"
        "    return np.asarray(make(3))\n"
    )
    helper_v1 = "def make(x):\n    return x\n"
    helper_v2 = (
        "import jax.numpy as jnp\n"
        "\n"
        "def make(x):\n"
        "    return jnp.zeros((4,))\n"
    )

    def run(helper_src):
        sources = {
            helper_rel: parse_source(helper_rel, helper_src),
            caller_rel: parse_source(caller_rel, caller_src),
        }
        return analyze_sources(sources)

    before = run(helper_v1)
    assert [f for f in before.findings if f.rule == "MTPU502"] == []

    after = run(helper_v2)
    caller_hits = [
        f
        for f in after.findings
        if f.rule == "MTPU502" and f.path == caller_rel
    ]
    assert len(caller_hits) == 1 and caller_hits[0].line == 5

    # the sound --changed-only trigger set: helper edit pulls in caller
    closure = after.graph.reverse_file_closure({helper_rel})
    assert caller_rel in closure
    restricted = [f for f in after.findings if f.path in closure]
    assert caller_hits[0] in restricted
    # naive per-file gating would have dropped it
    assert caller_hits[0].path not in {helper_rel}


def test_deviceflow_suppression_and_staleness_audit():
    """# noqa: MTPU501 silences a real finding; a stale MTPU5xx noqa is
    itself flagged by the pass's own MTPU106 audit."""
    lines = _fixture_lines("bad_mtpu501.py")
    rel = "tests/data/analysis/bad_mtpu501.py"
    idx = next(
        i for i, ln in enumerate(lines) if "VIOLATION: MTPU501" in ln
    )
    suppressed = list(lines)
    suppressed[idx] = suppressed[idx].split("#")[0].rstrip()
    suppressed[idx] += "  # noqa: MTPU501"
    text = "\n".join(suppressed) + "\n"
    rep = analyze_sources({rel: parse_source(rel, text)})
    from minio_tpu.analysis.findings import unused_suppressions as _aud

    audited = rep.findings + _aud(
        rel, text, rep.findings, prefixes=("MTPU5",)
    )
    found = filter_suppressed(audited, {rel: suppressed})
    assert found == [], "\n".join(f.render() for f in found)

    # stale: an MTPU5xx noqa on a code line where nothing fires (the
    # audit tokenizes, so it must sit on a real code line, not in the
    # docstring)
    stale = list(lines)
    stale_idx = next(
        i for i, ln in enumerate(stale) if ln.startswith("import ")
    )
    stale[stale_idx] += "  # noqa: MTPU502"
    stale_text = "\n".join(stale) + "\n"
    rep2 = analyze_sources({rel: parse_source(rel, stale_text)})
    audited2 = rep2.findings + _aud(
        rel, stale_text, rep2.findings, prefixes=("MTPU5",)
    )
    found2 = filter_suppressed(audited2, {rel: stale})
    assert any(
        f.rule == "MTPU106" and f.line == stale_idx + 1 for f in found2
    ), "\n".join(f.render() for f in found2)


def test_astcache_reparses_only_on_mtime_change(tmp_path):
    """The shared AST cache is (mtime, size)-keyed: same stamp serves
    the same object, an edit re-parses."""
    import os as _os

    rel_dir = tmp_path
    target = rel_dir / "mod.py"
    target.write_text("x = 1\n")
    from minio_tpu.analysis.astcache import AstCache

    cache = AstCache()
    rel = os.path.relpath(str(target), analysis.REPO_ROOT)
    first = cache.get(rel)
    again = cache.get(rel)
    assert first is again
    target.write_text("x = 2\n")
    _os.utime(str(target), ns=(1, 1))  # force a distinct stamp
    third = cache.get(rel)
    assert third is not first
    assert third.text == "x = 2\n"


# -- lifecycle pass (MTPU601-606) ---------------------------------------

from minio_tpu.analysis import lifecycle  # noqa: E402
from minio_tpu.analysis.resource_registry import Registry  # noqa: E402

# lifecycle matching is scope-gated, so every fixture is analyzed under
# a rel path inside the resource class it exercises
LIFECYCLE_REL_OVERRIDE = {
    "bad_mtpu601.py": "minio_tpu/server/bad_mtpu601.py",
    "good_mtpu601.py": "minio_tpu/server/good_mtpu601.py",
    "bad_mtpu602.py": "minio_tpu/dsync/bad_mtpu602.py",
    "good_mtpu602.py": "minio_tpu/dsync/good_mtpu602.py",
    "bad_mtpu603.py": "minio_tpu/dsync/bad_mtpu603.py",
    "good_mtpu603.py": "minio_tpu/dsync/good_mtpu603.py",
    "bad_mtpu604.py": "minio_tpu/parallel/bad_mtpu604.py",
    "good_mtpu604.py": "minio_tpu/parallel/good_mtpu604.py",
    "bad_mtpu605.py": "minio_tpu/dsync/bad_mtpu605.py",
    "good_mtpu605.py": "minio_tpu/dsync/good_mtpu605.py",
}


def _lifecycle_fixture(name):
    """Lifecycle-analyze one fixture under its in-scope rel path,
    noqa-filtered as the CLI would."""
    lines = _fixture_lines(name)
    rel = LIFECYCLE_REL_OVERRIDE.get(
        name, f"tests/data/analysis/{name}"
    )
    text = "\n".join(lines) + "\n"
    rep = lifecycle.analyze_sources({rel: parse_source(rel, text)})
    return filter_suppressed(rep.findings, {rel: lines})


def _knobs_module_source(*, family):
    lines = [
        "KNOBS = {",
        '    "MINIO_TPU_FIXTURE_REGISTERED": ("1", "fixture knob"),',
        "}",
        "PREFIX_KNOBS = {",
    ]
    if family:
        lines.append(
            '    "MINIO_TPU_FIXTURE_FAM_": ("", "fixture family"),'
        )
    lines.append("}")
    return "\n".join(lines) + "\n"


def _knob_fixture(name, *, family):
    """MTPU606-check one fixture against a synthetic knob registry
    (and a README stub mentioning every registered name)."""
    lines = _fixture_lines(name)
    rel = f"tests/data/analysis/{name}"
    sources = {
        rel: parse_source(rel, "\n".join(lines) + "\n"),
        lifecycle.KNOBS_REL: parse_source(
            lifecycle.KNOBS_REL, _knobs_module_source(family=family)
        ),
    }
    found = lifecycle.check_knobs(
        sources,
        readme_text=(
            "MINIO_TPU_FIXTURE_REGISTERED MINIO_TPU_FIXTURE_FAM_"
        ),
    )
    return filter_suppressed(found, {rel: lines})


@pytest.mark.parametrize(
    "name", [f"bad_mtpu60{i}.py" for i in range(1, 6)]
)
def test_bad_lifecycle_fixture_exact_findings(name):
    expected = _expected_markers(name)
    assert expected, f"{name} declares no VIOLATION markers"
    got = {(f.rule, f.line) for f in _lifecycle_fixture(name)}
    assert got == expected


@pytest.mark.parametrize(
    "name", [f"good_mtpu60{i}.py" for i in range(1, 6)]
)
def test_good_lifecycle_fixture_clean(name):
    found = _lifecycle_fixture(name)
    assert found == [], "\n".join(f.render() for f in found)


def test_bad_knob_fixture_exact_findings():
    expected = _expected_markers("bad_mtpu606.py")
    assert expected
    found = _knob_fixture("bad_mtpu606.py", family=False)
    got = {(f.rule, f.line) for f in found}
    assert got == expected, "\n".join(f.render() for f in found)


def test_good_knob_fixture_clean():
    found = _knob_fixture("good_mtpu606.py", family=True)
    assert found == [], "\n".join(f.render() for f in found)


def test_tree_lifecycle_clean():
    """minio_tpu/ carries zero unsuppressed lifecycle findings."""
    found = analysis.run_lifecycle()
    assert found == [], "\n".join(f.render() for f in found)


def test_mtpu605_flags_registered_def_missing_from_module():
    """Drift direction 1: the registry pins _RWLock.acquire_read (and
    friends) to dsync/namespace.py; a namespace.py that lost them must
    fire MTPU605 for each missing def — without direction-2 noise for
    the def that survives under its registered name."""
    rel = "minio_tpu/dsync/namespace.py"
    src = (
        "class _RWLock:\n"
        "    def acquire_write(self, key):\n"
        "        return True\n"
    )
    found = lifecycle.analyze_sources(
        {rel: parse_source(rel, src)}
    ).findings
    assert found, "a gutted namespace.py must not analyze clean"
    assert {f.rule for f in found} == {"MTPU605"}
    gone = ("acquire_read", "release_read", "release_write")
    for name in gone:
        assert any(
            f"_RWLock.{name}" in f.message for f in found
        ), name
    assert not any("acquire_write" in f.message for f in found)


def test_registry_resolves_every_def_in_tree_graph(tree_graph):
    """Every (module, qname) the resource registry names resolves to
    a call-graph def node — the registry cannot drift from the code
    (same closure discipline as the MTPU204 coverage test)."""
    _, graph = tree_graph
    missing = [
        (rel, qname)
        for res in Registry.default().resources
        for rel, qname in res.defs
        if graph.lookup(rel, qname) is None
    ]
    assert missing == []


def test_mtpu601_fires_on_seeded_backend_canary():
    """Canary: a copy of the REAL codec/backend.py with an exit that
    neither drains, releases nor hands on the parity ref it admitted to
    the plane cache - caught with exact rule id and line."""
    rel = "minio_tpu/codec/backend.py"
    src = _read_tree_source(rel)
    seeded = src + (
        "\n\ndef _canary_peek(plane):\n"
        "    ref = _DeviceParityRef(parity_plane_cache(), plane)\n"
        "    if ref.nbytes > (1 << 20):\n"
        "        return None  # canary: the plane stays in the cache\n"
        "    return ref\n"
    )
    clean = lifecycle.analyze_sources(
        {rel: parse_source(rel, src)}
    ).findings
    assert clean == [], "\n".join(f.render() for f in clean)
    found = lifecycle.analyze_sources(
        {rel: parse_source(rel, seeded)}
    ).findings
    leak_line = (
        seeded.splitlines().index(
            "        return None  # canary: the plane stays in the cache"
        )
        + 1
    )
    assert {(f.rule, f.line) for f in found} == {
        ("MTPU601", leak_line)
    }, "\n".join(f.render() for f in found)


def test_mtpu601_fires_on_seeded_admission_canary():
    """Canary: a copy of the REAL server/admission.py whose
    TokenCounter.try_acquire sheds without undoing its probe token
    leaks one slot per shed — caught at the shed return."""
    rel = "minio_tpu/server/admission.py"
    src = _read_tree_source(rel)
    target = (
        "        if 0 < limit < len(res):\n"
        "            try:\n"
        "                res.pop()\n"
    )
    assert src.count(target) == 1, "canary anchor drifted"
    idx = src.index(target)
    end = src.index("            return False\n", idx)
    seeded = (
        src[:idx]
        + "        if 0 < limit < len(res):\n"
        + "            return False  # canary: probe undo dropped\n"
        + src[end + len("            return False\n"):]
    )
    clean = lifecycle.analyze_sources(
        {rel: parse_source(rel, src)}
    ).findings
    assert clean == [], "\n".join(f.render() for f in clean)
    found = lifecycle.analyze_sources(
        {rel: parse_source(rel, seeded)}
    ).findings
    shed_line = (
        seeded.splitlines().index(
            "            return False  # canary: probe undo dropped"
        )
        + 1
    )
    assert {(f.rule, f.line) for f in found} == {
        ("MTPU601", shed_line)
    }, "\n".join(f.render() for f in found)


def test_lifecycle_reverse_closure_retriggers_caller_on_helper_edit():
    """Editing a CALLEE must re-trigger lifecycle on its callers: the
    helper starts as the release seam for the caller's admission token
    (caller clean via call-graph credit), then loses the release — the
    caller now leaks, and the helper's reverse-dependency closure must
    contain the caller so --changed-only reports it; naive per-file
    gating would silently skip it."""
    helper_rel = "minio_tpu/server/lc_helper.py"
    caller_rel = "minio_tpu/server/lc_caller.py"
    caller_src = (
        "from minio_tpu.server.lc_helper import finish\n"
        "\n"
        "\n"
        "def serve(adm, tenant):\n"
        "    if not adm.try_enter_tenant(tenant):\n"
        "        return 503\n"
        "    finish(adm, tenant)\n"
        "    return 200\n"
    )
    helper_v1 = (
        "def finish(adm, tenant):\n"
        "    adm.leave_tenant(tenant)\n"
    )
    helper_v2 = (
        "def finish(adm, tenant):\n"
        "    return (adm, tenant)\n"
    )

    def run(helper_src):
        sources = {
            helper_rel: parse_source(helper_rel, helper_src),
            caller_rel: parse_source(caller_rel, caller_src),
        }
        return lifecycle.analyze_sources(sources)

    before = run(helper_v1)
    assert before.findings == [], "\n".join(
        f.render() for f in before.findings
    )

    after = run(helper_v2)
    got = {(f.rule, f.path, f.line) for f in after.findings}
    assert got == {
        ("MTPU603", caller_rel, 7),
        ("MTPU601", caller_rel, 8),
    }, "\n".join(f.render() for f in after.findings)

    # the sound --changed-only trigger set: helper edit pulls in caller
    closure = after.graph.reverse_file_closure({helper_rel})
    assert caller_rel in closure
    restricted = [f for f in after.findings if f.path in closure]
    assert len(restricted) == 2


def test_lifecycle_suppression_and_staleness_audit():
    """# noqa: MTPU601 silences a real finding; a stale MTPU6xx noqa
    is itself flagged by the pass's own MTPU106 audit."""
    lines = _fixture_lines("bad_mtpu601.py")
    rel = LIFECYCLE_REL_OVERRIDE["bad_mtpu601.py"]
    idx = next(
        i for i, ln in enumerate(lines) if "VIOLATION: MTPU601" in ln
    )
    suppressed = list(lines)
    suppressed[idx] = suppressed[idx].split("#")[0].rstrip()
    suppressed[idx] += "  # noqa: MTPU601"
    text = "\n".join(suppressed) + "\n"
    rep = lifecycle.analyze_sources({rel: parse_source(rel, text)})
    audited = rep.findings + unused_suppressions(
        rel, text, rep.findings, prefixes=("MTPU6",)
    )
    found = filter_suppressed(audited, {rel: suppressed})
    assert found == [], "\n".join(f.render() for f in found)

    # stale: an MTPU6xx noqa on a code line where nothing fires
    stale = list(lines)
    stale_idx = next(
        i for i, ln in enumerate(stale) if ln.strip() == "return 503"
    )
    stale[stale_idx] += "  # noqa: MTPU602"
    stale_text = "\n".join(stale) + "\n"
    rep2 = lifecycle.analyze_sources(
        {rel: parse_source(rel, stale_text)}
    )
    audited2 = rep2.findings + unused_suppressions(
        rel, stale_text, rep2.findings, prefixes=("MTPU6",)
    )
    found2 = filter_suppressed(audited2, {rel: stale})
    assert any(
        f.rule == "MTPU106" and f.line == stale_idx + 1 for f in found2
    ), "\n".join(f.render() for f in found2)


# -- CLI contract -------------------------------------------------------


def _run_cli(*argv, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "minio_tpu.analysis", *argv],
        cwd=analysis.REPO_ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )


def test_cli_lint_pass_exits_zero_on_tree():
    r = _run_cli("--skip", "contracts", "locks")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stderr


def test_cli_exits_nonzero_on_bad_fixture():
    r = _run_cli(
        "--paths",
        "tests/data/analysis/bad_mtpu103.py",
        "--skip",
        "contracts",
        "locks",
    )
    assert r.returncode == 1
    assert "MTPU103" in r.stdout
    # findings render as path:line: RULE message
    assert re.search(
        r"tests/data/analysis/bad_mtpu103\.py:\d+: MTPU103", r.stdout
    )


def test_cli_json_is_machine_readable_and_stable():
    args = (
        "--json",
        "--paths",
        "tests/data/analysis/bad_mtpu101.py",
        "tests/data/analysis/bad_mtpu104.py",
        "--skip",
        "contracts",
        "locks",
        "deviceflow",
        "lifecycle",
    )
    r1 = _run_cli(*args)
    r2 = _run_cli(*args)
    assert r1.returncode == 1
    d1, d2 = json.loads(r1.stdout), json.loads(r2.stdout)
    assert set(d1) == {"findings", "passes", "callgraph"}
    # findings are deterministic; pass timings are wall-clock and not
    data = d1["findings"]
    assert data == d2["findings"], "findings must be deterministic"
    assert data == sorted(
        data,
        key=lambda d: (d["path"], d["line"], d["rule"], d["message"]),
    )
    assert {d["rule"] for d in data} == {"MTPU101", "MTPU104"}
    assert set(data[0]) == {"rule", "path", "line", "message"}
    assert set(d1["passes"]) == {"lint", "abi"}
    assert d1["callgraph"] is None  # deviceflow + lifecycle skipped


def test_cli_json_reports_timings_and_callgraph_stats():
    """--json carries per-pass wall seconds and the call-graph block
    when the interprocedural passes run."""
    r = _run_cli(
        "--json",
        "--paths",
        "tests/data/analysis/good_mtpu501.py",
        "--skip",
        "contracts",
        "locks",
        "abi",
    )
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(r.stdout)
    assert data["findings"] == []
    assert set(data["passes"]) == {"lint", "deviceflow", "lifecycle"}
    for secs in data["passes"].values():
        assert isinstance(secs, float) and secs >= 0.0
    cg = data["callgraph"]
    assert set(cg) == {"nodes", "edges", "boundary_edges", "seconds"}
    assert cg["nodes"] >= 1 and cg["seconds"] >= 0.0


def test_cli_list_rules():
    r = _run_cli("--list-rules")
    assert r.returncode == 0
    for rule in RULES:
        assert rule in r.stdout
    # the lifecycle rules are part of the published catalog
    for i in range(1, 7):
        assert f"MTPU60{i}" in r.stdout


def test_cli_skip_covers_the_abi_pass():
    r = _run_cli(
        "--skip", "abi", "contracts", "locks", "deviceflow", "lifecycle"
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[lint]" in r.stderr


def test_cli_changed_only_exits_zero():
    r = _run_cli("--changed-only", "--skip", "contracts", "locks")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "changed-only" in r.stderr


@pytest.mark.slow
def test_cli_full_run_is_clean():
    """All six passes through the real CLI (what CI would run), and
    the full run stays inside the 30s analyzer budget."""
    t0 = time.monotonic()
    r = _run_cli()
    wall = time.monotonic() - t0
    assert r.returncode == 0, r.stdout + r.stderr
    assert (
        "0 finding(s) "
        "[lint, abi, contracts, locks, deviceflow, lifecycle]"
        in r.stderr
    )
    assert wall < 30.0, f"full analyzer run took {wall:.1f}s (budget 30s)"
