"""Partition rules, the elastic compile seam, and submesh placement.

This module is the single source of truth for how the codec's logical
planes map onto mesh axes.  Three layers live here:

* **Partition rules** (``PARTITION_RULES``/``spec_for``): a declarative
  pattern -> ``PartitionSpec`` table in the style of fmengine's
  ``match_partition_rules``.  Kernels name their operand planes
  ("stripe_words", "parity_words", ...) and the rules resolve the
  sharding; nothing outside this file writes a ``PartitionSpec`` literal
  (enforced by lint rule MTPU109).

* **Compile seam** (``register_kernel``/``compile_kernel``): a
  Titanax-style memoized factory that picks the lowering per geometry.
  Kernels that need the XOR all-reduce, or whose per-device body may be
  a Pallas call (which XLA cannot partition), register a ``build_local``
  (per-device body for shard_map); pure-XLA collective-free kernels
  register a ``build_global`` and lower through plain ``jax.jit`` with
  ``NamedSharding`` in/out constraints.  The memo is keyed on the rules
  fingerprint, the mesh's *device ids* and axis shape, and the static
  geometry - so a rebuilt ``Mesh`` over the same devices hits the cache
  instead of silently recompiling (``Mesh`` equality is
  identity-flavored across re-creation).

* **Placement** (``PlacementRouter``/``placed``): carve the device set
  into submeshes and route independent merged batches to the
  least-loaded one instead of always spanning the mesh.  Policy comes
  from ``MINIO_TPU_PLACEMENT``:

  - ``span``:  always use every device (the pre-elastic behaviour);
  - ``route``: always place each batch on one submesh;
  - ``auto``  (default): route small batches, span once a batch is big
    enough to keep every device busy on the stripe axis.

  ``MINIO_TPU_SUBMESH_DEVICES`` sets the submesh width (default 1 chip).
  The routed device set travels to ``TpuBackend._mesh_for`` through a
  thread-local (``placed()``/``current_placement()``), so the batcher's
  per-submesh workers don't need to thread devices through the backend
  API.
"""

from __future__ import annotations

import hashlib
import os
import re
import threading
import warnings
from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Input donation on the CPU test platform is accepted but not honored;
# jax warns per-compile.  Mirrors the filter in ops/codec_step.py.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


# ---------------------------------------------------------------------------
# Partition rules: logical plane name -> PartitionSpec
# ---------------------------------------------------------------------------
#
# Plane naming: kernels declare operands by what the array *is*, not by
# position.  Batched planes are (B, rows, width): batch over "stripe",
# rows over "shard" when the k data shards are split across devices.
# Parity and reconstructed outputs are replicated over "shard" (every
# shard-group device holds the full parity, like every disk holding its
# own shard after the fan-out write).

PARTITION_RULES: tuple[tuple[str, PartitionSpec], ...] = (
    # (B, k, w|L) data planes: batch over stripe, shards over shard
    (
        r"^(stripe|data|survivor)_(batch|words|bytes)$",
        PartitionSpec("stripe", "shard", None),
    ),
    # (B, k, 8) per-data-shard digests follow their data rows
    (r"^data_digests$", PartitionSpec("stripe", "shard", None)),
    # (B, m, w|L) parity planes: replicated over shard after all-reduce
    (r"^parity_(words|bytes|plane)$", PartitionSpec("stripe", None, None)),
    (r"^parity_digests$", PartitionSpec("stripe", None, None)),
    # (B, k, w) reconstructed data: whole stripes, replicated over shard
    (r"^recon_words$", PartitionSpec("stripe", None, None)),
    # (B, n, w|8) quorum-read planes (fused verify+reconstruct): all n
    # shard rows of a stripe stay together - the bitrot check is
    # row-local but the decode needs every survivor row
    (r"^quorum_(words|digests)$", PartitionSpec("stripe", None, None)),
    # (B,) the stripes' true shard bytes follow their stripes; (R,) the
    # digest rows' follow their rows.  A length is an operand: the
    # planes lie at a staged width, the programs are one a width
    (r"^stripe_lengths$", PartitionSpec("stripe")),
    (r"^digest_lengths$", PartitionSpec(("stripe", "shard"))),
    # the loss pattern's operands (present bool[n], survivors int32[k],
    # matrix uint8[k, k]): a few bytes, whole on every device
    (r"^decode_(present|survivors)$", PartitionSpec(None)),
    (r"^decode_matrix$", PartitionSpec(None, None)),
    # (B, n) per-shard verify verdicts
    (r"^ok_mask$", PartitionSpec("stripe", None)),
    # (R, w) flattened digest rows: spread over every device on both axes
    (r"^digest_(rows|out)$", PartitionSpec(("stripe", "shard"), None)),
    # (k, L) sequence-parallel stream: length over every device
    (r"^seq_", PartitionSpec(None, ("stripe", "shard"))),
)


def spec_for(
    name: str,
    rules: tuple[tuple[str, PartitionSpec], ...] = PARTITION_RULES,
) -> PartitionSpec:
    """Resolve one logical plane name to its PartitionSpec.

    Raises ``KeyError`` on no match - a kernel naming a plane the rules
    don't cover is a bug, not a replicate-by-default.
    """
    for pattern, spec in rules:
        if re.search(pattern, name):
            return spec
    raise KeyError(f"no partition rule matches plane {name!r}")


def match_partition_rules(names, rules=PARTITION_RULES):
    """Resolve a pytree of plane names to a matching pytree of specs."""
    if isinstance(names, str):
        return spec_for(names, rules)
    return tuple(match_partition_rules(n, rules) for n in names)


_FINGERPRINT: list[str | None] = [None]


def rules_fingerprint(
    rules: tuple[tuple[str, PartitionSpec], ...] = PARTITION_RULES,
) -> str:
    """Stable digest of the rule table (part of the compile-cache key)."""
    if rules is PARTITION_RULES and _FINGERPRINT[0] is not None:
        return _FINGERPRINT[0]
    h = hashlib.sha256()
    for pattern, spec in rules:
        h.update(f"{pattern}->{tuple(spec)}\n".encode())
    fp = h.hexdigest()[:16]
    if rules is PARTITION_RULES:
        _FINGERPRINT[0] = fp
    return fp


# ---------------------------------------------------------------------------
# Compile seam: one memoized factory, two lowerings
# ---------------------------------------------------------------------------


class KernelDef:
    """One registered mesh kernel: plane names + geometry-specialized builders.

    ``build_local(mesh, **statics)`` returns the per-device body for a
    shard_map lowering (it may use collectives over mesh axes).
    ``build_global(mesh, **statics)`` returns a whole-array function for
    the jit+NamedSharding lowering (no collectives; XLA partitions it).
    Either may be None, but not both.
    """

    __slots__ = (
        "kind",
        "in_names",
        "out_names",
        "build_local",
        "build_global",
        "donate_argnums",
    )

    def __init__(
        self,
        kind,
        in_names,
        out_names,
        build_local,
        build_global,
        donate_argnums,
    ):
        self.kind = kind
        self.in_names = tuple(in_names)
        self.out_names = tuple(out_names)
        self.build_local = build_local
        self.build_global = build_global
        self.donate_argnums = tuple(donate_argnums)

    def in_specs(self, rules=PARTITION_RULES):
        return tuple(spec_for(n, rules) for n in self.in_names)

    def out_specs(self, rules=PARTITION_RULES):
        return tuple(spec_for(n, rules) for n in self.out_names)


_KERNELS: dict[str, KernelDef] = {}


def register_kernel(
    kind: str,
    *,
    in_names,
    out_names,
    build_local=None,
    build_global=None,
    donate_argnums=(),
) -> KernelDef:
    """Register a mesh kernel with the compile seam (idempotent by kind)."""
    if build_local is None and build_global is None:
        raise ValueError(f"kernel {kind!r} registered with no builder")
    kd = KernelDef(
        kind, in_names, out_names, build_local, build_global, donate_argnums
    )
    _KERNELS[kind] = kd
    return kd


def registered_kernels() -> tuple[str, ...]:
    """Kinds known to the seam (the MTPU204 closure set for mesh kernels)."""
    return tuple(sorted(_KERNELS))


def kernel_def(kind: str) -> KernelDef:
    return _KERNELS[kind]


def mesh_cache_key(mesh: Mesh) -> tuple:
    """Identity-free mesh key: device ids + axis shape + axis names."""
    return (
        tuple(int(d.id) for d in mesh.devices.flat),
        tuple(mesh.devices.shape),
        tuple(mesh.axis_names),
    )


_compile_mu = threading.Lock()
_compiled: dict[tuple, tuple[object, str]] = {}
_cache_stats = {"hits": 0, "misses": 0}


def _single(tree):
    return tree[0] if len(tree) == 1 else tree


def _pick_mode(kd: KernelDef, mesh: Mesh) -> str:
    if kd.build_global is None:
        return "shard_map"
    if kd.build_local is None:
        return "jit"
    # both lowerings available: shard_map only pays off when the shard
    # axis actually needs the XOR all-reduce; otherwise let XLA
    # partition the whole-array program (no collectives to hand-roll)
    shard_n = dict(zip(mesh.axis_names, mesh.devices.shape)).get("shard", 1)
    return "shard_map" if shard_n > 1 else "jit"


def compile_kernel(
    kind: str, mesh: Mesh, *, force_mode: str | None = None, **statics
):
    """Compile (or fetch) one kernel for one geometry.

    Cache key: (kind, rules fingerprint, device ids + axis shape,
    force_mode, sorted statics) - NOT the Mesh object, so a rebuilt mesh
    over the same devices reuses the compiled executable.
    """
    kd = _KERNELS[kind]
    key = (
        kind,
        rules_fingerprint(),
        mesh_cache_key(mesh),
        force_mode,
        tuple(sorted(statics.items())),
    )
    with _compile_mu:
        hit = _compiled.get(key)
        if hit is not None:
            _cache_stats["hits"] += 1
            return hit[0]
    mode = force_mode or _pick_mode(kd, mesh)
    in_specs = kd.in_specs()
    out_specs = kd.out_specs()
    if mode == "jit":
        step = kd.build_global(mesh, **statics)
        fn = jax.jit(
            step,
            in_shardings=_single(
                tuple(NamedSharding(mesh, s) for s in in_specs)
            ),
            out_shardings=_single(
                tuple(NamedSharding(mesh, s) for s in out_specs)
            ),
            donate_argnums=kd.donate_argnums,
        )
    elif mode == "shard_map":
        step = kd.build_local(mesh, **statics)
        fn = jax.jit(
            jax.shard_map(
                step,
                mesh=mesh,
                in_specs=_single(in_specs),
                out_specs=_single(out_specs),
                check_vma=False,
            ),
            donate_argnums=kd.donate_argnums,
        )
    else:
        raise ValueError(f"unknown lowering mode {mode!r}")
    with _compile_mu:
        prior = _compiled.get(key)
        if prior is not None:
            # lost a build race; keep the first executable
            _cache_stats["hits"] += 1
            return prior[0]
        _compiled[key] = (fn, mode)
        _cache_stats["misses"] += 1
    return fn


def kernel_mode(kind: str, mesh: Mesh, **statics) -> str:
    """The lowering the seam would pick (compiles lazily as a side effect)."""
    kd = _KERNELS[kind]
    return _pick_mode(kd, mesh)


def cache_info() -> dict:
    with _compile_mu:
        return {
            "entries": len(_compiled),
            "hits": _cache_stats["hits"],
            "misses": _cache_stats["misses"],
        }


def clear_compile_cache() -> None:
    with _compile_mu:
        _compiled.clear()
        _cache_stats["hits"] = 0
        _cache_stats["misses"] = 0


# ---------------------------------------------------------------------------
# Placement: submesh carving + least-loaded routing
# ---------------------------------------------------------------------------

PLACEMENT_POLICIES = ("span", "route", "auto")


def placement_policy() -> str:
    pol = os.environ.get("MINIO_TPU_PLACEMENT", "auto").strip().lower()
    return pol if pol in PLACEMENT_POLICIES else "auto"


class Submesh:
    """One carved slice of the device set with a live queue-depth count."""

    __slots__ = ("name", "devices", "depth")

    def __init__(self, name: str, devices: tuple):
        self.name = name
        self.devices = devices
        self.depth = 0

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"Submesh({self.name}, n={len(self.devices)}, depth={self.depth})"


class PlacementRouter:
    """Route independent merged batches to the least-loaded submesh.

    The device set is carved into contiguous submeshes of
    ``submesh_devices`` chips (``MINIO_TPU_SUBMESH_DEVICES``, default 1);
    a remainder that can't fill a submesh folds into the last one.
    ``route`` returns None when the batch should span the full mesh
    (policy ``span``, a single submesh, or ``auto`` with a batch big
    enough to occupy every device on the stripe axis).
    """

    def __init__(self, devices, policy: str | None = None,
                 submesh_devices: int | None = None):
        self.devices = tuple(devices)
        if policy is None:
            policy = placement_policy()
        self.policy = policy if policy in PLACEMENT_POLICIES else "auto"
        if submesh_devices is None:
            try:
                submesh_devices = int(
                    os.environ.get("MINIO_TPU_SUBMESH_DEVICES", "1") or "1"
                )
            except ValueError:
                submesh_devices = 1
        width = max(1, min(submesh_devices, len(self.devices)))
        subs = []
        full = (len(self.devices) // width) * width
        for lo in range(0, full, width):
            subs.append(
                Submesh(f"sub{len(subs)}", self.devices[lo:lo + width])
            )
        if full < len(self.devices):
            if subs:
                last = subs[-1]
                subs[-1] = Submesh(
                    last.name, last.devices + self.devices[full:]
                )
            else:  # pragma: no cover - width clamped to len(devices)
                subs.append(Submesh("sub0", self.devices))
        self._subs = tuple(subs)
        self._mu = threading.Lock()

    @property
    def submeshes(self) -> tuple[Submesh, ...]:
        return self._subs

    def route(self, batch_blocks: int) -> Submesh | None:
        """Claim a submesh for a batch (None -> span the full mesh)."""
        if self.policy == "span" or len(self._subs) <= 1:
            return None
        if self.policy == "auto" and batch_blocks >= len(self.devices):
            # enough stripes to occupy every device data-parallel: the
            # span path's stripe axis beats any single submesh
            return None
        with self._mu:
            sub = min(self._subs, key=lambda s: s.depth)
            sub.depth += 1
            return sub

    def release(self, sub: Submesh) -> None:
        with self._mu:
            sub.depth = max(0, sub.depth - 1)

    def depths(self) -> dict[str, int]:
        with self._mu:
            return {s.name: s.depth for s in self._subs}


_placement_tls = threading.local()


def current_placement():
    """The device set routed to this thread, or None (span)."""
    return getattr(_placement_tls, "devices", None)


@contextmanager
def placed(devices):
    """Scope mesh construction on this thread to a routed device set."""
    prev = getattr(_placement_tls, "devices", None)
    _placement_tls.devices = tuple(devices)
    try:
        yield
    finally:
        _placement_tls.devices = prev
