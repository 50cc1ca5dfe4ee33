"""CLI entry: ``python -m minio_tpu.server [--address host:port] args...``

The `minio server` analogue (cmd/server-main.go): each positional arg is
one zone; ellipses patterns expand to that zone's drives - bare paths
(``/data/disk{1...8}``) for single-node mode or URLs
(``http://host{1...2}:9000/data/disk{1...4}``) for distributed mode.
Local drives are served to peers over the storage REST plane; remote
drives are reached through StorageRESTClient; format.json is
created/quorum-loaded per zone with a boot retry loop, and the object
layer is Zones(Sets(Objects)) exactly like newObjectLayer
(server-main.go:559-567).  HTTP serving starts before the object layer
is ready (503 ServerNotInitialized until then), mirroring
server-main.go:477-484.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys


def group_zone_args(zone_args: list[str]) -> list[list[str]]:
    """Group CLI drive args into zones (createServerEndpoints,
    endpoint-ellipses.go:331): args WITHOUT ellipses all join one zone
    (verify-healing.sh lists endpoints individually); each arg WITH an
    ellipses pattern is its own zone (server-pool syntax).  Mixing the
    two styles is rejected, like the reference."""
    from ..utils import ellipses

    with_e = [a for a in zone_args if ellipses.has_ellipses(a)]
    if not with_e:
        return [list(zone_args)]
    if len(with_e) != len(zone_args):
        raise SystemExit(
            "all drive args must use ellipses patterns, or none"
        )
    return [ellipses.expand(a) for a in zone_args]


def build_object_layer(zone_args: list[str], parity: "int | None" = None):
    """Single-node convenience: expand bare-path args -> zones layer."""
    ol, _ = build_cluster(zone_args, local_port=0, secret="", parity=parity)
    return ol


def build_cluster(
    zone_args: list[str],
    local_port: int,
    secret: str,
    parity: "int | None" = None,
    format_timeout_s: float = 120.0,
    local_disk_map: "dict | None" = None,
    nslock=None,
):
    """Expand args -> local XLStorage + remote REST disks -> zones layer.

    Returns (object_layer, local_disks) where local_disks is every
    XLStorage this node owns (to be served on the storage REST plane).
    """
    from ..cluster.endpoints import resolve_endpoints
    from ..objectlayer.format import wait_for_format
    from ..objectlayer.sets import ErasureSets
    from ..objectlayer.zones import ErasureZones
    from ..storage.rest_client import StorageRESTClient
    from ..storage.xl import XLStorage
    from ..utils import ellipses

    # standalone FS mode: exactly one local drive and no cluster
    # topology (newObjectLayer FS selection, server-main.go:561-564).
    # A drive already carrying an erasure format must never be
    # reinterpreted as FS (that would misread xl-layout data).
    flat = [a for g in group_zone_args(zone_args) for a in g]
    if len(flat) == 1 and "://" not in flat[0]:
        import os as _os

        if _os.path.exists(
            _os.path.join(flat[0], ".sys", "format.json")
        ):
            raise SystemExit(
                f"{flat[0]} holds an erasure format; a single-drive FS "
                "server cannot serve it (add the original drives)"
            )
        from ..objectlayer.fs import FSObjects

        return FSObjects(flat[0]), []

    zones = []
    local_disks: list = []
    for specs in group_zone_args(zone_args):
        eps = resolve_endpoints(specs, local_port)
        if len(eps) < 2:
            raise SystemExit(
                f"zone {specs!r} expands to {len(eps)} drives; need >= 2"
            )
        set_count, drives_per_set = ellipses.layout(len(eps))
        disks = []
        for ep in eps:
            if ep.is_local:
                d = (local_disk_map or {}).get(ep.path)
                if d is None:
                    d = XLStorage(ep.path, endpoint=ep.raw)
                local_disks.append(d)
                disks.append(d)
            else:
                disks.append(
                    StorageRESTClient(ep.host, ep.port, ep.path, secret)
                )
        # only the owner of the first endpoint may mint a fresh cluster
        init_allowed = eps[0].is_local
        ref_fmt, ordered = wait_for_format(
            disks,
            set_count,
            drives_per_set,
            init_allowed=init_allowed,
            timeout_s=format_timeout_s,
        )
        # per-op disk identity validation on local drives
        # (xl-storage-disk-id-check.go): a swapped drive fails fast.
        # Metering sits INSIDE the identity check so the heal
        # subsystem's one-hop `unwrapped` probe of unformatted drives
        # still reaches the raw disk (storage/metered.py docstring).
        from ..storage import metered
        from ..storage.diskcheck import DiskIDCheck

        guarded = []
        for i, d in enumerate(ordered):
            if d is not None and d.is_local():
                s_idx, d_idx = divmod(i, drives_per_set)
                guarded.append(
                    DiskIDCheck(
                        metered.wrap(d), ref_fmt.sets[s_idx][d_idx]
                    )
                )
            else:
                guarded.append(d)
        zones.append(
            ErasureSets(
                guarded,
                set_count,
                drives_per_set,
                parity_blocks=parity,
                nslock=nslock,
                format_ref=ref_fmt,
            )
        )
    return ErasureZones(zones), local_disks


def start_background_heal(ol):
    """MRF queue + heal routine + fresh-disk monitor over the object
    layer (startBackgroundOps analogue, server-main.go:524).  Returns
    (routine, monitor); both are daemon threads."""
    from ..heal.background import FreshDiskMonitor, HealQueue, HealRoutine

    queue = HealQueue()
    routine = HealRoutine(
        ol,
        queue,
        throttle_s=float(
            os.environ.get("MINIO_TPU_HEAL_THROTTLE_S") or 0.0
        ),
    ).start()
    monitor = FreshDiskMonitor(
        ol,
        queue,
        interval_s=float(
            os.environ.get("MINIO_TPU_FRESH_DISK_INTERVAL_S") or 10.0
        ),
    ).start()
    for zone in ol.zones:
        for eset in zone.sets:
            eset.heal_hook = queue.push_object
    return routine, monitor


def cluster_nodes(zone_args: list[str], local_port: int):
    """Sorted unique (host, port, is_local) across every URL endpoint -
    the lock-plane topology (one locker per node, like newLockAPI per
    endpoint host)."""
    from ..cluster.endpoints import resolve_endpoints

    nodes: dict = {}
    for specs in group_zone_args(zone_args):
        for ep in resolve_endpoints(specs, local_port):
            if ep.is_url:
                nodes[(ep.host, ep.port)] = (
                    nodes.get((ep.host, ep.port), False) or ep.is_local
                )
    return [
        (h, p, nodes[(h, p)]) for h, p in sorted(nodes)
    ]


def build_lock_plane(
    zone_args: list[str], local_port: int, secret: str
):
    """(nslock, lock_rest_server, maintenance) for this topology.

    Single-node (or bare-path) layouts use the in-process NamespaceLock;
    multi-node layouts get dsync quorum locks over the lock REST plane
    with refresh + expiry recovery (see dsync/drwmutex.py).
    """
    from ..dsync import drwmutex
    from ..dsync.local_locker import LocalLocker, LockMaintenance
    from ..dsync.lock_rest import LockRESTClient, LockRESTServer
    from ..dsync.namespace import DistNamespaceLock, NamespaceLock

    nodes = cluster_nodes(zone_args, local_port)
    if len(nodes) <= 1:
        return NamespaceLock(), None, None
    refresh_s = float(
        os.environ.get("MINIO_TPU_LOCK_REFRESH_S")
        or drwmutex.REFRESH_INTERVAL_S
    )
    expiry_s = float(
        os.environ.get("MINIO_TPU_LOCK_EXPIRY_S") or drwmutex.EXPIRY_S
    )
    local = LocalLocker(endpoint=f"local:{local_port}")
    lockers = [
        local
        if is_local
        else LockRESTClient(host, port, secret)
        for host, port, is_local in nodes
    ]
    ds = drwmutex.Dsync(lockers, refresh_interval_s=refresh_s)
    maint = LockMaintenance(
        local, interval_s=max(1.0, expiry_s / 3), expiry_s=expiry_s
    ).start()
    return (
        DistNamespaceLock(ds),
        LockRESTServer(local, secret),
        maint,
    )


def run_gateway(args) -> int:
    """Serve the S3 API over a non-erasure backend
    (cmd/gateway/gateway-main.go).  No storage/lock planes, no heal,
    no crawler - the backend owns durability."""
    from .http import S3Server

    if len(args.zones) != 3:
        raise SystemExit(
            "usage: server gateway {nas <path> | s3 <endpoint-url>}"
        )
    kind, target = args.zones[1], args.zones[2]
    if kind == "nas":
        from ..objectlayer.fs import FSObjects

        ol = FSObjects(target)
        desc = f"NAS gateway over {target}"
    elif kind == "s3":
        from ..gateway.s3 import S3Objects

        ol = S3Objects(
            target,
            os.environ.get("MINIO_TPU_GATEWAY_ACCESS_KEY")
            or args.access_key,
            os.environ.get("MINIO_TPU_GATEWAY_SECRET_KEY")
            or args.secret_key,
            region=args.region,
        )
        desc = f"S3 gateway to {target}"
    else:
        raise SystemExit(f"unknown gateway backend {kind!r}")
    srv = S3Server(
        ol,
        address=args.address,
        access_key=args.access_key,
        secret_key=args.secret_key,
        region=args.region,
    )
    from ..iam.sys import IAMSys

    # IAM rides the backend for nas (persistent), memory for s3 (the
    # upstream bucket namespace is not ours to write into)
    iam = IAMSys(
        args.access_key,
        args.secret_key,
        ol if kind == "nas" else None,
    )
    srv.attach_iam(iam)
    srv.start()
    print(f"minio-tpu serving {desc} at {srv.endpoint}")
    sys.stdout.flush()
    stop = signal.sigwait([signal.SIGINT, signal.SIGTERM])
    print(f"signal {stop}, shutting down")
    srv.shutdown()
    return 0


def resolve_codec_backend() -> None:
    """Resolve the codec backend NOW and say once what it runs on.

    Done at boot rather than on the first PUT so that a chip that is
    missing, or held by another process (``jax.devices()`` raises),
    stops the server here instead of surfacing as 500s - or worse, as
    a quiet switch to the host codec.
    """
    from ..codec import backend as backend_mod
    from ..utils import log

    try:
        info = backend_mod.backend_info()
    except RuntimeError as e:
        # what jax.devices() and get_backend raise; one line, exit 1
        raise SystemExit(
            f"minio-tpu: codec backend unavailable, not starting: {e}"
        ) from e
    fields = {
        k: v
        for k, v in info.items()
        if k not in ("devices", "compile_cache")
    }
    if "compile_cache" in info:
        fields["compile_cache_dir"] = info["compile_cache"]["dir"]
    print(
        "minio-tpu codec "
        + " ".join(f"{k}={v!r}" for k, v in fields.items())
    )
    sys.stdout.flush()
    log.logger("server").info("codec backend", extra=log.kv(**fields))


def main(argv=None) -> int:
    from ..utils import jaxenv

    jaxenv.setup_compile_cache()  # before the first JAX use

    p = argparse.ArgumentParser(prog="minio-tpu server")
    p.add_argument(
        "zones",
        nargs="+",
        help=(
            "one arg per zone; ellipses expand: /data/disk{1...8} or "
            "http://host{1...2}:9000/data/disk{1...4}"
        ),
    )
    p.add_argument("--address", default="0.0.0.0:9000")
    p.add_argument(
        "--access-key",
        default=os.environ.get("MINIO_ACCESS_KEY", "minioadmin"),
    )
    p.add_argument(
        "--secret-key",
        default=os.environ.get("MINIO_SECRET_KEY", "minioadmin"),
    )
    p.add_argument("--region", default="us-east-1")
    p.add_argument(
        "--parity", type=int, default=None,
        help="parity drives per set (default: half)",
    )
    p.add_argument(
        "--format-timeout", type=float, default=120.0,
        help="seconds to wait for peers during format bootstrap",
    )
    args = p.parse_args(argv)

    # sigwait only *claims* a signal that is blocked; an unblocked
    # SIGTERM races the default disposition (immediate termination) and
    # usually loses, skipping the graceful drain below.  Block both
    # before any thread spawns so every thread inherits the mask.
    signal.pthread_sigmask(
        signal.SIG_BLOCK, {signal.SIGINT, signal.SIGTERM}
    )

    from ..utils import log

    log.setup(os.environ.get("MINIO_TPU_LOG_LEVEL", "info"))

    # gateway mode (cmd/gateway/): `server gateway nas /path` or
    # `server gateway s3 http://upstream:9000`
    if args.zones and args.zones[0] == "gateway":
        return run_gateway(args)

    resolve_codec_backend()

    from ..cluster.endpoints import resolve_endpoints
    from ..storage.rest_server import StorageRESTServer
    from ..storage.rest_common import PREFIX as STORAGE_PREFIX
    from ..storage.xl import XLStorage
    from ..utils import ellipses
    from .http import S3Server

    local_port = int(args.address.rsplit(":", 1)[1])

    # Discover local drives first so the storage plane can serve peers
    # BEFORE format bootstrap (reference starts HTTP at
    # server-main.go:477, then waits for disks).
    # With MINIO_TPU_FAULT_INJECTION=1 each local drive is wrapped in a
    # FaultDisk at the bottom of the wrap chain
    # (DiskIDCheck(Metered(Fault(XL)))), and the admin fault endpoint
    # can schedule delay/error/corrupt/hang rules on it remotely - the
    # chaos-grid harness degrades nodes it does not share memory with.
    fault_on = (os.environ.get("MINIO_TPU_FAULT_INJECTION") or "") in (
        "1",
        "on",
        "true",
    )
    fault_seed = int(os.environ.get("MINIO_TPU_FAULT_SEED") or 0)
    fault_disks: dict = {}
    pre_local: list = []
    local_map: dict = {}
    for specs in group_zone_args(args.zones):
        for ep in resolve_endpoints(specs, local_port):
            if ep.is_local:
                d = XLStorage(ep.path, endpoint=ep.raw)
                if fault_on:
                    from ..storage.faults import FaultDisk

                    d = FaultDisk(
                        d, seed=fault_seed + len(fault_disks)
                    )
                    fault_disks[str(d.unwrapped.root)] = d
                pre_local.append(d)
                local_map[ep.path] = d

    srv = S3Server(
        None,  # object layer attaches after bootstrap
        address=args.address,
        access_key=args.access_key,
        secret_key=args.secret_key,
        region=args.region,
        internode_secret=args.secret_key,
    )
    if fault_disks:
        srv.fault_disks = fault_disks
    # readiness gate: /minio/health/ready stays 503 until every
    # subsystem flips its flag, so a harness polls instead of sleeping
    srv.boot_status = {
        "lock_plane": False,
        "boot": False,
        "server_loops": False,
    }
    storage_rest = StorageRESTServer(pre_local, args.secret_key)
    srv.register_internode(STORAGE_PREFIX, storage_rest.handle)
    nslock, lock_rest, _lock_maint = build_lock_plane(
        args.zones, local_port, args.secret_key
    )
    if lock_rest is not None:
        from ..dsync.lock_rest import PREFIX as LOCK_PREFIX

        srv.register_internode(LOCK_PREFIX, lock_rest.handle)
    srv.boot_status["lock_plane"] = True

    # peer control plane + bootstrap handshake (distributed mode):
    # every node serves /minio-tpu/peer/v1 and verifies the cluster
    # config fingerprint against every peer before joining
    from ..cluster import peer as peer_mod

    fingerprint = peer_mod.cluster_fingerprint(
        args.zones, args.access_key, args.secret_key
    )
    peers = [
        peer_mod.PeerRESTClient(host, port, args.secret_key)
        for host, port, is_local in cluster_nodes(args.zones, local_port)
        if not is_local
    ]
    peer_rest = peer_mod.PeerRESTServer(
        srv,
        args.secret_key,
        fingerprint=fingerprint,
        local_locker=lock_rest.locker if lock_rest is not None else None,
    )
    srv.register_internode(peer_mod.PREFIX, peer_rest.handle)
    srv.peer_rest = peer_rest  # shutdown() closes its sweeper
    srv.local_locker = lock_rest.locker if lock_rest is not None else None
    if peers:
        srv.peer_notifier = peer_mod.PeerNotifier(peers)
        # tiered read cache: object mutations on this node drop every
        # peer's cached groups through the notifier fan-out
        from .. import cache as rcache_mod

        rcache_mod.set_broadcast(
            srv.peer_notifier.read_cache_invalidated
        )

    srv.start()
    # listener shards are up (async plane: every MINIO_TPU_SERVER_LOOPS
    # loop accepting; readiness() additionally reports per-loop state)
    srv.boot_status["server_loops"] = (
        srv._plane is None or srv._plane.loops_ready()
    )
    print(f"minio-tpu listening at {srv.endpoint} (bootstrapping)")
    if peers:
        peer_mod.verify_cluster(
            peers, fingerprint, timeout_s=args.format_timeout
        )
        print(f"bootstrap handshake ok with {len(peers)} peer(s)")

    ol, _ = build_cluster(
        args.zones,
        local_port,
        args.secret_key,
        args.parity,
        format_timeout_s=args.format_timeout,
        local_disk_map=local_map,
        nslock=nslock,
    )
    # optional SSD read cache in front of the object layer
    # (disk-cache.go CacheObjectLayer, server-main.go:531-540)
    from ..objectlayer.cache import cache_from_env

    ol_front = cache_from_env(ol)
    if ol_front is not ol:
        print("disk cache enabled")
    srv.object_layer = ol_front
    # federation: a shared record dir plays etcd's role for bucket
    # DNS (cmd/config/etcd/dns); every cluster pointing at the same
    # dir shares one global bucket namespace
    fed_dir = os.environ.get("MINIO_TPU_FEDERATION_DIR", "")
    if fed_dir:
        from ..cluster.dns import BucketDNS, FileDNSStore

        adv_host = (
            os.environ.get("MINIO_TPU_FEDERATION_HOST")
            or args.address.rsplit(":", 1)[0]
        )
        if adv_host in ("0.0.0.0", ""):
            adv_host = "127.0.0.1"
        srv.bucket_dns = BucketDNS(
            FileDNSStore(fed_dir),
            adv_host,
            local_port,
            scheme=(
                "https"
                if (os.environ.get("MINIO_TPU_TLS") or "").lower()
                in ("1", "on", "true")
                else "http"
            ),
        )
        print(f"federation: bucket DNS at {fed_dir} as "
              f"{adv_host}:{local_port}")
    # once formats are known, the storage REST plane serves the
    # DiskIDCheck-wrapped disks too: peer I/O must not write onto a
    # swapped drive either (xl-storage-disk-id-check.go applies to the
    # server side of the plane)
    from ..storage.diskcheck import DiskIDCheck as _DIC

    guarded_map = {}
    for zone in getattr(ol, "zones", []):
        for eset in zone.sets:
            for d in eset.disks:
                if isinstance(d, _DIC):
                    guarded_map[d.unwrapped.root] = d
    storage_rest.guard_disks(guarded_map)
    # persisted KV config: load + apply before subsystems read their
    # env seams (initSafeMode config load, server-main.go:526)
    srv.config.apply()
    # store-backed IAM after the object layer is up (iam.go:419 Init)
    from ..iam.sys import IAMSys

    iam = IAMSys(args.access_key, args.secret_key, ol)
    srv.attach_iam(iam)
    if peers:
        iam.start_refresher(
            float(os.environ.get("MINIO_TPU_IAM_REFRESH_S") or 120.0)
        )
    if getattr(ol, "zones", None):
        _heal_routine, _disk_monitor = start_background_heal(ol)
        srv.heal_routine = _heal_routine
        srv.heal_queue = _heal_routine.queue
        srv.disk_monitor = _disk_monitor  # reloadformat peer RPC
    # data-update tracker: object mutations mark a persisted bloom
    # journal the crawler uses to skip clean buckets
    # (data-update-tracker.go:63)
    from ..crawler import updatetracker as ut_mod

    tracker_root = next(iter(guarded_map), None) or getattr(
        ol, "root", None
    )
    tracker = ut_mod.DataUpdateTracker(
        path=os.path.join(tracker_root, ".sys", "update-tracker.bin")
        if tracker_root
        else None
    )
    ut_mod.install_tracker(tracker)
    srv.update_tracker = tracker
    notifier = getattr(srv, "peer_notifier", None)

    def _cluster_bloom(oldest: int, current: int):
        """Union of this node's filter and every peer's; any
        unreachable/trackerless peer poisons completeness so the
        crawler falls back to a full sweep."""
        resp = tracker.cycle_filter(oldest, current)
        if notifier is not None:
            for wire in notifier.cycle_blooms(oldest, current):
                if wire is None:
                    resp.complete = False
                    continue
                peer_resp = ut_mod.BloomResponse.from_wire(wire)
                resp.complete = resp.complete and peer_resp.complete
                try:
                    resp.filter.union_into(peer_resp.filter)
                except ValueError:
                    resp.complete = False
        return resp

    # data crawler: usage accounting + lifecycle enforcement
    # (runDataCrawler, server-main.go:524 startBackgroundOps)
    from ..crawler import DataCrawler
    from ..objectlayer.api import META_BUCKET

    srv.crawler = DataCrawler(
        ol,
        srv.bucket_meta,
        interval_s=float(
            os.environ.get("MINIO_TPU_CRAWL_INTERVAL_S") or 60.0
        ),
        events=srv.events,
        ensure_event_rules=srv.ensure_event_rules,
        replication=srv.replication,
        cycle_bloom=_cluster_bloom,
        # heal-on-crawl: full sweeps probe shard health and feed the
        # MRF heal queue (data scanner healObject path)
        heal_hook=(
            srv.heal_queue.push_object
            if getattr(srv, "heal_queue", None) is not None
            else None
        ),
        # distributed: elect one sweeping node per cycle via the lock
        # plane (single node: the local _crawl_mu already serializes)
        leader_lock=(
            (
                lambda: nslock.write(
                    META_BUCKET, "data-crawler/leader", timeout=2.0
                )
            )
            if peers
            else None
        ),
    ).start()
    si = ol.storage_info()
    if "zones" in si:
        desc = (
            f"{len(ol.zones)} zone(s) "
            f"{[z['disks'] for z in si['zones']]} drives"
        )
        zcount = len(ol.zones)
    else:
        desc = "standalone FS backend (1 drive)"
        zcount = 0
    srv.boot_status["boot"] = True
    print(f"minio-tpu serving {desc} at {srv.endpoint}")
    sys.stdout.flush()
    log.logger("server").info(
        "online",
        extra=log.kv(endpoint=srv.endpoint, zones=zcount),
    )
    # serving: from here the codec loads the programs of every shard
    # width the traffic shows behind it, off the requests' path
    from ..codec import backend as backend_mod

    backend_mod.start_warming()
    stop = signal.sigwait([signal.SIGINT, signal.SIGTERM])
    print(f"signal {stop}, shutting down")
    backend_mod.stop_warming()
    # graceful teardown order: drain in-flight requests first (their
    # handlers release their own locks), stop heal/crawler/monitor
    # threads (inside srv.shutdown), THEN unwind whatever dsync grants
    # remain so peers see clean releases instead of waiting out the
    # expiry window on orphaned entries.
    tracker.save()  # flush marks recorded since the last rotation
    srv.shutdown()
    if _lock_maint is not None:
        _lock_maint.stop()
    if hasattr(nslock, "release_all"):
        released = nslock.release_all()
        if released:
            print(f"released {released} held lock(s)")
    print("shutdown complete")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
