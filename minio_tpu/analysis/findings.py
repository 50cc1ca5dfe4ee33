"""Findings, the rule catalog, and inline suppression.

The project-native analogue of Go's vet/staticcheck diagnostics: every
analyzer pass (hot-path lint, kernel contract checker, lock-order
auditor) emits ``Finding`` records carrying a stable ``MTPU###`` rule id
so future PRs can diff reports, gate CI on exact rule sets, and suppress
individual sites with ``# noqa: MTPU###`` where a violation is a
documented, deliberate exception.
"""

from __future__ import annotations

import dataclasses
import re

# Rule catalog.  1xx = hot-path lint (AST), 2xx = kernel contract
# checker (abstract eval), 3xx = lock-order auditor (runtime shim),
# 4xx = ctypes/ABI contract checker (native FFI seam).
RULES: "dict[str, str]" = {
    "MTPU101": (
        "host-device sync (block_until_ready / jax.device_get / .item() / "
        "np.asarray of a traced value) inside jit-traced code or a "
        "device-only module"
    ),
    "MTPU102": (
        "retrace bomb: jax.jit function takes a non-array Python "
        "parameter (int/str/bool/bytes/float/tuple annotation) not "
        "routed through static_argnames/static_argnums"
    ),
    "MTPU103": (
        "silently swallowed failure: `except Exception/BaseException/"
        "bare except` whose body is only pass/..."
    ),
    "MTPU104": (
        "prometheus metric-name convention: family must be "
        "miniotpu_-prefixed lowercase, counters must end in _total"
    ),
    "MTPU105": (
        "prometheus label-key hygiene: label keys must match "
        "[a-z_][a-z0-9_]*"
    ),
    "MTPU106": (
        "unused suppression: a `# noqa: MTPU###` whose rule does not "
        "fire on that line (stale suppressions rot; silence MTPU106 "
        "itself on the line to keep one deliberately)"
    ),
    "MTPU107": (
        "eager parity readback: np.asarray/np.array/jax.device_get of a "
        "device parity output outside the *_end/drain seams in "
        "minio_tpu/ops or codec/backend.py (re-introduces the D2H "
        "round-trip the digest-only PUT path removed)"
    ),
    "MTPU108": (
        "event-loop-blocking call inside an async def under "
        "minio_tpu/server/: time.sleep, raw socket send/recv, or a "
        "Future.result()/Event.wait() that is not awaited (one stalled "
        "coroutine stalls every connection; route blocking work through "
        "the worker-pool bridge)"
    ),
    "MTPU109": (
        "hand-written PartitionSpec literal in minio_tpu/parallel or "
        "minio_tpu/ops outside parallel/rules.py: shardings must come "
        "from the partition-rule table (rules.spec_for), the single "
        "source of truth the compile seam fingerprints"
    ),
    "MTPU110": (
        "object-data mutation outside the read-cache invalidation seam: "
        "a function in objectlayer/erasure_object.py or "
        "erasure_multipart.py that calls rename_data/delete_version (or "
        "delete_file/write_metadata/update_metadata on a non-SYS_VOL "
        "volume) must also call the invalidation seam "
        "(_invalidate_read_cache / cache.invalidate_object), or peers "
        "serve stale cached groups and FileInfo"
    ),
    "MTPU111": (
        "eager S3-Select readback: np.asarray/np.array/jax.device_get in "
        "s3select/device.py outside the result-drain seam (functions "
        "whose name contains 'drain'); only candidate row bytes may "
        "cross D2H, or the pushdown degenerates into a whole-plane "
        "host scan"
    ),
    "MTPU201": "kernel contract: wrong output dtype from a jitted entry point",
    "MTPU202": "kernel contract: wrong output shape from a jitted entry point",
    "MTPU203": (
        "kernel contract: encode->reconstruct shape round-trip broken"
    ),
    "MTPU204": (
        "kernel contract: jitted entry point in minio_tpu/ops has no "
        "registered contract check"
    ),
    "MTPU301": "lock-order cycle in the observed acquisition graph",
    "MTPU302": (
        "blocking call (sleep / socket connect / subprocess) while "
        "holding a registered hot-path lock"
    ),
    "MTPU401": (
        "ABI contract: ctypes binding arity differs from the native "
        "export's C parameter count (or annotation disagrees with the "
        "C signature)"
    ),
    "MTPU402": (
        "ABI contract: argtypes/restype drift between a ctypes binding "
        "and the export's declared `// @ctypes` annotation"
    ),
    "MTPU403": (
        "ABI contract: exported symbol with no ctypes binding, or a "
        "binding for a symbol the library does not export"
    ),
    "MTPU404": (
        "ABI contract: buffer pointer passed to native code with a "
        "length argument computed from a different array's shape"
    ),
    "MTPU405": (
        "ABI contract: numpy buffer reaches .ctypes.data_as() without "
        "contiguity evidence (ascontiguousarray/require/flags assert)"
    ),
    "MTPU501": (
        "device dataflow: use-after-donate — a value passed at a "
        "donate_argnums position of a registered donating entry point "
        "is read again afterwards (XLA may alias the donated buffer "
        "into an output; the PR 14 bug class, caught statically)"
    ),
    "MTPU502": (
        "device dataflow: interprocedural D2H escape — a "
        "device-provenance value (return of a registered jitted entry "
        "point, through any chain of calls) reaches np.asarray / "
        "bytes() / .item() / jax.device_get outside a registered drain "
        "seam (whole-tree generalization of MTPU107/111)"
    ),
    "MTPU503": (
        "device dataflow: device value captured across a thread/loop "
        "boundary (iopool.submit*, worker-pool submit/spawn_stream, "
        "run_coroutine_threadsafe, run_in_executor, Thread(target=)) "
        "without materialization — the D2H becomes a hidden sync on an "
        "arbitrary thread"
    ),
    "MTPU504": (
        "device dataflow: call-graph-deep blocking-under-async — a "
        "blocking call (time.sleep, raw socket I/O, Future.result(), "
        "non-asyncio .wait()) in a sync function reachable from a "
        "minio_tpu/server async def through plain calls, so it runs on "
        "the event loop (MTPU108 one-or-more frames deep; worker-pool "
        "boundary edges exempt the sanctioned sync-def bridges)"
    ),
    "MTPU505": (
        "device dataflow: registry drift — kernel_contracts declares a "
        "jitted entry point, donation position, or drain seam the tree "
        "does not have, or the tree declares one the registry misses "
        "(the MTPU403 orphan-check discipline for dataflow facts)"
    ),
    "MTPU601": (
        "resource lifecycle: leaked acquire — a registered resource "
        "(admission token, parity ref, "
        "io-pool future, rw-lock, fault hang) is acquired and a path "
        "reaches function exit without a matching release or a "
        "registered ownership transfer (the defer-less leak class: one "
        "missed release starves the device budget or wedges admission)"
    ),
    "MTPU602": (
        "resource lifecycle: double release — the same acquisition is "
        "released twice on one path (over-release corrupts the ledger "
        "or admission counters as silently as a leak)"
    ),
    "MTPU603": (
        "resource lifecycle: unprotected hold — an acquired resource is "
        "held across a raisable call without a try/finally (or `with`) "
        "guaranteeing its release; an exception on that call leaks the "
        "resource even though the straight-line path releases it"
    ),
    "MTPU604": (
        "resource lifecycle: use after ownership transfer — a resource "
        "handed to a registered transfer seam (async handle, band "
        "adopt, caller-owned return) is released or re-used afterwards "
        "by the original holder"
    ),
    "MTPU605": (
        "resource lifecycle: registry drift — resource_registry names "
        "an acquire/release/transfer function the call graph does not "
        "have, or an acquire-shaped API in a registered resource module "
        "has no registry entry (the MTPU505 discipline for lifecycle "
        "facts)"
    ),
    "MTPU606": (
        "config-knob drift: a MINIO_TPU_* environment knob is read "
        "without a minio_tpu/config/knobs.py registry entry, or a "
        "registered knob is missing its README mention, or a registry "
        "entry names a knob nothing reads (docs, defaults, and code "
        "move together)"
    ),
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One diagnostic: rule id + location + message.

    ``path`` is repo-relative where the finding is file-anchored;
    runtime passes anchor at the closest code object they can name.
    """

    rule: str
    path: str
    line: int
    message: str

    def sort_key(self):
        return (self.path, self.line, self.rule, self.message)

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
        }

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


_NOQA_RE = re.compile(
    r"#\s*noqa(?::\s*(?P<codes>[A-Z]+[0-9]+(?:\s*,\s*[A-Z]+[0-9]+)*))?",
)


def noqa_codes_for_line(line: str) -> "set[str] | None":
    """Suppression codes on a source line.

    Returns None when the line carries no noqa directive, the empty set
    for a bare ``# noqa`` (suppress everything), else the specific codes.
    """
    m = _NOQA_RE.search(line)
    if m is None:
        return None
    codes = m.group("codes")
    if not codes:
        return set()
    return {c.strip() for c in codes.split(",")}


def filter_suppressed(
    findings: "list[Finding]", source_lines: "dict[str, list[str]]"
) -> "list[Finding]":
    """Drop findings whose source line carries a matching noqa.

    ``source_lines`` maps finding paths to their file's lines; findings
    for paths not in the map (runtime findings) pass through untouched.
    """
    out = []
    for f in findings:
        lines = source_lines.get(f.path)
        if lines is not None and 1 <= f.line <= len(lines):
            codes = noqa_codes_for_line(lines[f.line - 1])
            if codes is not None and (not codes or f.rule in codes):
                continue
        out.append(f)
    return out


# Only codes of the file-anchored passes are audited for staleness: 1xx
# (lint) and 4xx (ABI) anchor at source lines, so "does it fire here"
# is well-defined — the deviceflow pass audits its own 5xx codes the
# same way, passing its prefix explicitly.  Foreign codes (BLE001,
# F401, ...) belong to other tools; MTPU106 on a line is the sanctioned
# keep-this-suppression escape hatch and MTPU100 is the syntax-error
# sentinel.
_AUDITED_PREFIXES = ("MTPU1", "MTPU4")
_AUDIT_EXEMPT = ("MTPU100", "MTPU106")


def unused_suppressions(
    rel_path: str,
    text: str,
    raw_findings: "list[Finding]",
    prefixes: "tuple[str, ...]" = _AUDITED_PREFIXES,
) -> "list[Finding]":
    """MTPU106: noqa'd MTPU rules that do not fire on their line.

    ``raw_findings`` must be PRE-noqa-filter findings for this file
    from every file-anchored pass whose codes ``prefixes`` covers —
    otherwise a working suppression looks unused.  Comments are found
    with tokenize, so a ``# noqa:`` inside a docstring is ignored.
    """
    import io
    import tokenize

    fired: "dict[int, set[str]]" = {}
    for f in raw_findings:
        fired.setdefault(f.line, set()).add(f.rule)
    out: "list[Finding]" = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(text).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return out  # broken files are MTPU100's problem, not ours
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        codes = noqa_codes_for_line(tok.string)
        if not codes:
            continue  # no noqa, or a bare one (out of audit scope)
        line = tok.start[0]
        for code in sorted(codes):
            if not code.startswith(prefixes):
                continue
            if code in _AUDIT_EXEMPT:
                continue
            if code not in fired.get(line, ()):
                out.append(
                    Finding(
                        "MTPU106",
                        rel_path,
                        line,
                        f"unused suppression: {code} does not fire on "
                        "this line; drop the noqa (or add MTPU106 to "
                        "it to keep deliberately)",
                    )
                )
    return out
