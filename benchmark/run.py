#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Makes fresh drive directories (on memory where they fit), starts the real
``python -m minio_tpu.server`` through ``launcher.py`` with the program's
defaults, fills the object pool through the served path, warms up every batch
size, keeps the cell's traffic running until the server has stopped compiling,
measures for ``--seconds``, checks the answers and the drives against the
plain reference, stops the server and prints one JSON line.  This process
never imports JAX: the server child owns the chip.

Exit codes: 0 a result line was printed (``correct`` may be false); 2 the
command cannot run here (no ``minio_tpu`` package beside it, a kernel
interpreter forced by the environment, an unknown workload); 3 JAX found no
TPU, or fewer chips than the cell asks for.  Without a result line nothing is
printed on standard output.

``--rehearse-cpu`` is the sandbox rehearsal over XLA:CPU at whatever size the
traffic file gives; every line it prints says ``platform=cpu`` and none of its
numbers is a statement about a chip.  The command in ``BENCHMARK.json`` cannot
reach it.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import http.client  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import generator  # noqa: E402
import readers  # noqa: E402
import reference  # noqa: E402
from s3 import Client  # noqa: E402

GENERATOR_CORE_SHARE = 3  # one core in three is the generator's: 4 of 13, 10 of 30
WARM_CLIENT_COUNTS = (1, 2, 4, 8, 16)  # and the cell's own count
WARM_ROUNDS = 2
MIN_RUN_UP = 10.0  # seconds of the cell's traffic before a window may open
QUIET_SECONDS = 6.0  # the compile counters have to stand still this long
QUIET_CAP = 90.0  # ... and the window opens regardless after this long
TRACE_AFTER = 3.0  # the traced slice starts this long after the window opens
TRACE_SECONDS = 5.0
LATE_ANSWERS = 60.0  # wait this long past the close for answers still due
SAMPLE_OBJECTS = 4  # objects decoded from their shards after the window
# kernels that have a Pallas form.  Every object of a cell is tile-aligned, so in the
# window their passes are Pallas ones; the few that are not are the server's own small
# metadata objects (ragged widths).  Sound runs read 0 to 0.01, a run whose aligned
# widths took the portable branch reads 1 (PERF.md, section 2).
PALLAS_KERNELS = ("encode_words_fused1", "reconstruct_words_batch",
                  "mesh_encode_hash", "mesh_reconstruct")
PORTABLE_LIMIT = 0.2
PALLAS_TILE_BYTES = 16384  # of one shard row (ops/rs_pallas.py: 4096 words)


class CannotRun(Exception):
    def __init__(self, code: int, why: str):
        super().__init__(why)
        self.code = code


class Run:
    """What the readers read."""

    t_process_start = T_PROCESS_START
    ks_open = ks_close = ks_trace_open = ks_trace_close = trace = sample = None

    def __init__(self, cell: dict, config: dict, traffic: dict):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.records: list = []
        self.marks: "list[dict]" = []
        self.device: dict = {}
        self.t0 = self.t1 = 0.0


def split_cores() -> "tuple[list[int], list[int]]":
    """(server cores, generator cores): disjoint, the generator's last."""
    cores = sorted(os.sched_getaffinity(0))
    n_gen = max(1, len(cores) // GENERATOR_CORE_SHARE)
    return cores[:-n_gen] or cores, cores[-n_gen:]


def wait_quiet(read_counter, quiet_s: float, cap_s: float, min_s: float,
               clock=time.monotonic, sleep=time.sleep, poll_s: float = 0.5) -> "tuple[bool, float]":
    """Return once ``read_counter()`` has not moved for ``quiet_s`` seconds
    (and at least ``min_s`` have passed): (True, waited).  After ``cap_s`` the
    wait ends regardless: (False, waited)."""
    began = changed = clock()
    last = read_counter()
    while True:
        sleep(poll_s)
        now, value = clock(), read_counter()
        if value != last:
            last, changed = value, now
        if now - changed >= quiet_s and now - began >= min_s:
            return True, now - began
        if now - began >= cap_s:
            return False, now - began


def memory_root(need_bytes: int) -> "tuple[str, str]":
    """A directory on memory for the drives when the pool fits in half of the
    free RAM, else the temporary directory: (path, medium)."""
    mounts = []
    with open("/proc/mounts") as f:
        for line in f:
            _, where, kind = line.split()[:3]
            mounts.append((where, kind))
    with open("/proc/meminfo") as f:
        free_ram = {ln.split(":")[0]: int(ln.split()[1]) * 1024 for ln in f}["MemAvailable"]

    def kind_of(path: str) -> str:
        path = os.path.realpath(path)
        best = max((m for m in mounts if path == m[0] or path.startswith(m[0].rstrip("/") + "/")),
                   key=lambda m: len(m[0]), default=("", "?"))
        return best[1]

    for cand in (tempfile.gettempdir(), "/dev/shm"):
        if (os.path.isdir(cand) and kind_of(cand) in ("tmpfs", "ramfs")
                and need_bytes < free_ram // 2
                and shutil.disk_usage(cand).free > 2 * need_bytes):
            return cand, "tmpfs"
    return tempfile.gettempdir(), "disk"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """The server child, started through the benchmark's launcher."""

    def __init__(self, workdir: str, config: dict, cores: "list[int]", rehearse: bool,
                 trace_dir: "str | None", fault: "str | None"):
        self.port = free_port()
        self.log_path = os.path.join(workdir, "server.log")
        self.drives = []
        zones = []
        for z in range(config["sets"]):
            n = config["drives_per_set"]
            root = os.path.join(workdir, f"set{z}")
            dirs = [os.path.join(root, f"d{i}") for i in range(1, n + 1)]
            for d in dirs:
                os.makedirs(d)
            self.drives.append(dirs)
            zones.append(dirs if config["sets"] == 1 else
                         [os.path.join(root, "d{1...%d}" % n)])
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("MINIO_TPU_") and k not in ("MINIO_ERASURE_BACKEND",)}
        env.update(config.get("env", {}))
        env.update(PYTHONPATH=REPO, PYTHONUNBUFFERED="1", JAX_LOG_COMPILES="1")
        env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache"))
        if rehearse:
            env.update(MINIO_ERASURE_BACKEND="tpu", JAX_PLATFORMS="cpu")
        cmd = [sys.executable, os.path.join(HERE, "launcher.py"),
               "--cores", ",".join(map(str, cores))]
        if trace_dir:
            cmd += ["--trace-dir", trace_dir]
        if fault:
            cmd += ["--break", fault]
        cmd += ["--", "--address", f"127.0.0.1:{self.port}", *config["server_args"],
                *[a for z in zones for a in z]]
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=self._log,
                                     stderr=subprocess.STDOUT)

    def log_tail(self, n: int = 1500) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()[-n:]

    def log_size(self) -> int:
        return os.path.getsize(self.log_path)

    def compile_lines(self, lo: int, hi: int) -> "list[str]":
        """What JAX logged about compiling between two sizes of the log."""
        with open(self.log_path, "rb") as f:
            f.seek(lo)
            text = f.read(hi - lo).decode(errors="replace")
        return [ln.strip() for ln in text.splitlines() if "Compiling " in ln]

    def wait_ready(self, timeout: float = 600.0) -> None:
        import http.client

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                text = self.log_tail()
                # the program refuses to start without its chip: that is "no TPU"
                code = 3 if "jax" in text.lower() or "tpu" in text.lower() else 2
                raise CannotRun(code, f"server exited {self.proc.returncode} at boot: {text}")
            try:
                c = http.client.HTTPConnection("127.0.0.1", self.port, timeout=5)
                c.request("GET", "/minio/health/ready")
                ok = c.getresponse().status == 200
                c.close()
                if ok:
                    return
            except OSError:
                pass
            time.sleep(0.2)
        raise CannotRun(2, f"server not ready in {timeout:.0f} s: {self.log_tail()}")

    def stop(self) -> "int | None":
        """SIGTERM and wait; the code it exited with, None if it had to be killed."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                self._log.close()
                return None
        self._log.close()
        return self.proc.returncode


class Workers:
    """The generator's processes and the pipes to them."""

    def __init__(self, traffic: dict, seed: int, port: int, cores: "list[int]"):
        ctx = multiprocessing.get_context("spawn")
        n = generator.n_workers(traffic)
        closed = traffic["loop"] == "closed"
        self.conns, self.procs = [], []
        for w in range(n):
            if closed:
                owners = [c for c in range(traffic["clients"]) if c % n == w]
                lane_numbers = owners
            else:
                owners = [w]
                lane_numbers = [c for c in range(traffic["clients"]) if c % n == w]
            spec = {"traffic": traffic, "seed": seed, "host": "127.0.0.1", "port": port,
                    "owners": owners, "lanes": len(lane_numbers), "lane_numbers": lane_numbers,
                    "worker": w, "workers": n, "cores": cores}
            here, there = ctx.Pipe()
            p = ctx.Process(target=generator.worker_main, args=(there, spec), daemon=True)
            p.start()
            there.close()
            self.conns.append(here)
            self.procs.append(p)
        self.live: "dict[str, tuple[int, int]]" = {}
        self.reconnects = 0

    def send(self, *msg) -> None:
        for c in self.conns:
            c.send(msg)

    def collect(self, timeout: float) -> "tuple[list, list[dict]]":
        """The answer of every worker to the phase just asked for."""
        records, marks = [], []
        self.live = {}
        deadline = time.monotonic() + timeout
        for c, p in zip(self.conns, self.procs):
            while not c.poll(1.0):
                if not p.is_alive():
                    raise RuntimeError(f"generator process {p.pid} died (exit {p.exitcode})")
                if time.monotonic() > deadline:
                    raise RuntimeError("a generator process did not answer in time")
            tag, recs, mk, live, reconnects = c.recv()
            records += [generator.Record(*r) for r in recs]
            marks.append(mk)
            self.live.update(live)
            self.reconnects += reconnects
        return records, marks

    def phase(self, *msg, timeout: float = 900.0) -> list:
        self.send(*msg)
        return self.collect(timeout)[0]

    def freeze(self) -> None:
        self.send("freeze")
        for c in self.conns:
            c.recv()

    def stop(self) -> None:
        for c, p in zip(self.conns, self.procs):
            try:
                c.send(("quit",))
            except (BrokenPipeError, OSError):
                pass
        for p in self.procs:
            p.join(timeout=20)
            if p.is_alive():
                p.kill()
                p.join()


def load_cell(workload: str) -> "tuple[dict, dict, dict, dict]":
    path = os.path.join(REPO, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise CannotRun(2, f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO, conf["file"])) as f:
        config = json.load(f)
    traffic = generator.load_traffic(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def read_metric(name: str, run: Run) -> "float | None":
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def failed_requests(records: list) -> list:
    return [r for r in records if r.failed or r.wrong]


def check_sample(run: Run, server: Server, workers: Workers, seed: int, admin: Client,
                 tainted: "set[str]", say) -> dict:
    """After the window: objects drawn from the seed are looked up on the
    drives, decoded from k of their n shards by the reference, and read once
    more through the server after one shard block was altered on its drive."""
    e, g = run.config["erasure"], run.config["guarantees"]
    k, m = e["data"], e["parity"]
    put_in_window = sorted({r.key for r in readers.completed(run) if r.kind == "PUT"}
                           & set(workers.live) - tainted)
    pool = put_in_window or sorted(set(workers.live) - tainted)
    rng = random.Random(f"{seed}/sample")
    keys = rng.sample(pool, min(SAMPLE_OBJECTS, len(pool)))
    if put_in_window and put_in_window[-1] not in keys:
        keys[-1] = put_in_window[-1]
    payloads = generator.Payloads(seed, sorted({int(s) for s, _ in run.traffic["sizes"]}))
    drives = [d for dirs in server.drives for d in dirs]
    out = {"objects": len(keys), "decode_mismatch": 0, "shards_short": 0, "bitrot_served": 0,
           "stored_bytes": 0, "user_bytes": 0, "shards_min": k + m}
    for key in keys:
        version, size = workers.live[key]
        want = payloads.whole(key, version, size)
        parts = reference.shards_on_drives(drives, generator.BUCKET, key)
        out["shards_min"] = min(out["shards_min"], len(parts))
        if len(parts) < g["write_quorum"]:
            out["shards_short"] += 1
            say(f"check: {key} v{version} is on {len(parts)} drives, write quorum is "
                f"{g['write_quorum']}")
            continue
        # any k of n: as many parity shards as there are, the rest drawn from the seed
        parity = [i for i in parts if i >= k]
        use = sorted(parity + rng.sample([i for i in parts if i < k], k - len(parity)))
        try:
            got = reference.decode_object(parts, use, size, k, m, e["block_size"])
        except ValueError as err:
            got = None
            say(f"check: {key}: {err}")
        if got != want:
            out["decode_mismatch"] += 1
            say(f"check: {key} v{version} decoded from shards {use} differs from its PUT")
        out["stored_bytes"] += reference.stored_bytes(parts)
        out["user_bytes"] += size
        # bitrot: alter one byte of a data shard on its drive, then read through the server
        victim = rng.choice([i for i in parts if i < k])
        at = reference.FRAME_DIGEST + min(1000, -(-min(size, e["block_size"]) // k) // 2)
        with open(parts[victim], "r+b") as f:
            f.seek(at)
            b = f.read(1)
            f.seek(at)
            f.write(bytes([b[0] ^ 0xFF]))
        try:
            r = admin.request("GET", f"/{generator.BUCKET}/{key}")
            status, same = r.status, r.body == want
        except (OSError, http.client.HTTPException) as err:
            admin.close()
            status, same = type(err).__name__, False
        if status != 200 or not same:
            out["bitrot_served"] += 1
            say(f"check: {key} read back wrong (status {status}) after shard {victim} "
                "was altered on its drive")
    return out


def main(argv: "list[str]") -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--break", dest="fault", help=argparse.SUPPRESS)
    ap.add_argument("--keep-trace", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    tag = "platform=cpu " if args.rehearse_cpu else ""

    def say(text: str) -> None:
        print(f"{tag}benchmark: {text}", file=sys.stderr, flush=True)

    server = workers = workdir = None
    try:
        if os.environ.get("MINIO_TPU_CODEC_INTERPRET"):
            raise CannotRun(2, "MINIO_TPU_CODEC_INTERPRET is set: an interpreted kernel "
                            "says nothing about the chip")
        if not os.path.isfile(os.path.join(REPO, "minio_tpu", "server", "__main__.py")):
            raise CannotRun(2, f"no minio_tpu package in {REPO}: run from a checkout")
        bench, cell, config, traffic = load_cell(args.workload)
        if args.rehearse_cpu:  # the same phases at a cut size
            traffic = dict(traffic, sizes=[[min(int(s), 1 << 20), w] for s, w in traffic["sizes"]],
                           pool_objects=min(traffic["pool_objects"],
                                            max(64, 4 * generator.n_owners(traffic))))
        run = Run(cell, config, traffic)
        server_cores, gen_cores = split_cores()
        os.sched_setaffinity(0, gen_cores)
        e = config["erasure"]
        pool_bytes = int(traffic["pool_objects"] * max(s for s, _ in traffic["sizes"])
                         * (e["data"] + e["parity"]) / e["data"])
        root, medium = memory_root(2 * pool_bytes)
        workdir = tempfile.mkdtemp(prefix="minio_tpu_bench_", dir=root)
        trace_dir = os.path.join(workdir, "trace") if args.trace else None
        if trace_dir:
            os.makedirs(trace_dir)
        say(f"cell {cell['name']} seed {args.seed}: server cores {server_cores}, generator "
            f"cores {gen_cores}, drives on {medium} under {root}")
        server = Server(workdir, config, server_cores, args.rehearse_cpu, trace_dir, args.fault)
        server.wait_ready()
        admin = Client("127.0.0.1", server.port)
        ks = admin.admin("kernel-stats")
        dev = ks["device"]
        if dev.get("platform") != "tpu" and not args.rehearse_cpu:
            raise CannotRun(3, f"the server's codec runs on backend {dev.get('backend')!r}, "
                            f"platform {dev.get('platform')!r}: JAX found no TPU")
        if dev["device_count"] < cell["chips"] and not args.rehearse_cpu:
            raise CannotRun(3, f"the cell needs {cell['chips']} chips, JAX found "
                            f"{dev['device_count']}")
        r = admin.request("PUT", f"/{generator.BUCKET}")
        if r.status != 200:
            raise RuntimeError(f"make bucket -> {r.status} {r.body[:200]!r}")

        # the server writes small objects of its own (usage, update tracker) about once
        # a minute: one of that width now, so that their program is not first met later
        tiny = b"t" * 200
        for method, body in (("PUT", (tiny,)), ("DELETE", ())):
            admin.request(method, f"/{generator.BUCKET}/warm-tiny", body=body,
                          body_sha256=hashlib.sha256(b"".join(body)).hexdigest())

        # ---- set-up: fill, degrade, warm every batch size -----------------
        workers = Workers(traffic, args.seed, server.port, gen_cores)
        t = time.monotonic()
        setup_failed = failed_requests(workers.phase("fill"))
        say(f"pool of {traffic['pool_objects']} objects filled in {time.monotonic() - t:.1f} s")
        for drive in traffic.get("lost_drives", []):
            # a lost drive is gone for good: its path stops being a directory, so
            # nothing can be read from it and the heal cannot write it back
            for dirs in server.drives:
                shutil.rmtree(dirs[drive - 1])
                open(dirs[drive - 1], "w").close()
        t = time.monotonic()
        kinds = [k for k in ("PUT", "GET") if traffic["mix"].get(k)]
        # an open loop's "clients" are its pool of connections, never all busy at once
        counts = sorted({c for c in WARM_CLIENT_COUNTS if c < traffic["clients"]}
                        | ({traffic["clients"]} if traffic["loop"] == "closed" else set()))
        for count in counts:
            for kind in kinds:
                setup_failed += failed_requests(workers.phase(
                    "burst", kind, count, WARM_ROUNDS, time.monotonic() + 0.05))
        say(f"warmed client counts {counts} x {kinds} in {time.monotonic() - t:.1f} s; "
            f"compile counters {ks_compiles(admin)}")
        workers.freeze()

        # ---- the cell's traffic runs; the window opens once nothing compiles --
        workers.send("run", time.monotonic() + 0.2)
        quiet, waited = wait_quiet(lambda: ks_compiles(admin), QUIET_SECONDS, QUIET_CAP,
                                   MIN_RUN_UP)
        say(f"compile counters quiet: {quiet} after {waited:.1f} s of the cell's traffic")
        run.t0 = time.monotonic() + 0.3
        run.t1 = run.t0 + args.seconds
        workers.send("stop_at", run.t1)
        sleep_until(run.t0)
        workers.send("mark", "open")
        run.ks_open = admin.admin("kernel-stats")
        log_open = server.log_size()
        if trace_dir:
            sleep_until(run.t0 + min(TRACE_AFTER, args.seconds / 4))
            run.ks_trace_open = admin.admin("kernel-stats")
            open(os.path.join(trace_dir, "on"), "w").close()
            sleep_until(time.monotonic() + min(TRACE_SECONDS, args.seconds / 2))
            os.remove(os.path.join(trace_dir, "on"))
            run.ks_trace_close = admin.admin("kernel-stats")
        sleep_until(run.t1)
        workers.send("mark", "close")
        run.ks_close = admin.admin("kernel-stats")
        log_close = server.log_size()
        run.records, run.marks = workers.collect(LATE_ANSWERS + 30)
        health = admin.admin("kernel-stats")["device"]
        peaks = [d.get("peak_bytes_in_use") or 0 for d in health["devices"]]
        run.device = {"platform": health["platform"], "kind": health["device_kind"],
                      "count": health["device_count"], "memory_peak_bytes": max(peaks)}
        in_window = [r for r in run.records if run.t0 <= r.due <= run.t1
                     or run.t0 <= r.end <= run.t1]
        bad = failed_requests(run.records)
        tainted = {r.key for r in bad + setup_failed}
        for r in (bad + setup_failed)[:10]:
            say(f"request at fault: {r.kind} {r.key} status {r.status} "
                f"failed={r.failed} wrong={r.wrong}")
        for ln in server.compile_lines(log_open, log_close)[:20]:
            say(f"compiled inside the window: {ln[:300]}")

        # ---- correct: the answers, the drives, the kernels that ran ----------
        run.sample = check_sample(run, server, workers, args.seed, admin, tainted, say)
        passes = sum(run.ks_close["device_passes"].get(k, 0) - run.ks_open["device_passes"].get(k, 0)
                     for k in PALLAS_KERNELS)
        pallas = sum(run.ks_close["pallas_passes"].get(k, 0) - run.ks_open["pallas_passes"].get(k, 0)
                     for k in PALLAS_KERNELS)
        # the Pallas kernels take widths that are whole tiles; other widths are portable by design
        aligned = not args.rehearse_cpu and all(
            -(-int(s) // e["data"]) % PALLAS_TILE_BYTES == 0 for s, _ in traffic["sizes"])
        exit_code = None
        if trace_dir:
            deadline = time.monotonic() + 120
            while not os.path.exists(os.path.join(trace_dir, "done")):
                if time.monotonic() > deadline:
                    raise RuntimeError("the server never finished writing its trace")
                time.sleep(0.1)
        workers.stop()
        exit_code = server.stop()
        compared = {
            "answers_compared": [sum(1 for r in run.records if r.status > 0), ">= 1"],
            "wrong_answers": [sum(1 for r in run.records if r.wrong) + sum(
                1 for r in setup_failed if r.wrong), 0],
            "unanswered": [sum(1 for r in run.records + setup_failed
                               if r.failed and r.status != 503), 0],
            "decode_mismatch": [run.sample["decode_mismatch"], 0],
            "shards_short": [run.sample["shards_short"], 0],
            "bitrot_served": [run.sample["bitrot_served"], 0],
            "sampled_objects": [run.sample["objects"], ">= 1"],
            "kernel_passes": [passes, ">= 1"],
            "portable_share": [1.0 - pallas / passes if passes and aligned else 0.0,
                               PORTABLE_LIMIT],
            "server_exit": [-1 if exit_code is None else exit_code, 0],
        }
        correct = all(v >= 1 if limit == ">= 1" else v <= limit
                      for v, limit in compared.values())
        if trace_dir:
            run.trace = reduce_trace(trace_dir, say, args.keep_trace)
            if run.trace:
                run.device["busy_s"] = run.trace["busy_s"]
                run.device["window_s"] = run.trace["window_s"]

        # ---- the line -------------------------------------------------------
        wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
        metrics = {}
        for m in wanted:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            value = read_metric(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line = {}
        if args.rehearse_cpu:
            line["rehearsal"] = "platform=cpu"
        line.update(correct=correct, attempted=len(in_window),
                    failed=len([r for r in in_window if r.failed or r.wrong]),
                    metrics=metrics, device=run.device)
        if run.trace:
            line["breakdown"] = {"device_ops": run.trace["device_ops"],
                                 "idle_gaps": run.trace["idle_gaps"]}
        line["notes"] = {"quiet": quiet, "run_up_s": waited, "drives": medium,
                         "reconnects": workers.reconnects,
                         "window_compiles": readers.window_compiles(run),
                         "compile_cache": run.ks_close["device"]["compile_cache"],
                         "hedge": run.ks_close.get("hedge"),
                         "gen_busy": readers.gen_busy(run),
                         "latency_ms": readers.latency_summary(run),
                         "shards_min": run.sample["shards_min"]}
        line["compared"] = compared
        for name, (value, limit) in compared.items():
            say(f"compared {name} = {value} (limit {limit})")
        say(f"correct = {correct}")
        print(json.dumps(line), flush=True)
        return 0
    except CannotRun as err:
        say(str(err))
        return err.code
    finally:
        if workers is not None:
            workers.stop()
        if server is not None:
            server.stop()
        if workdir is not None:
            shutil.rmtree(workdir, ignore_errors=True)


def ks_compiles(admin: Client) -> int:
    return readers.compiles(admin.admin("kernel-stats"))


def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def reduce_trace(trace_dir: str, say, keep: "str | None") -> "dict | None":
    """The reduction runs in a child held to the CPU: this process stays off JAX."""
    found = [os.path.join(d, f) for d, _, files in os.walk(trace_dir)
             for f in files if f.endswith(".xplane.pb")]
    if not found:
        say("no .xplane.pb was written")
        return None
    if keep:
        shutil.copy(found[0], keep)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(HERE, "trace_reduce.py"), found[0]],
                       env=env, capture_output=True, text=True, timeout=240)
    if p.returncode != 0:
        say(f"trace reduction failed: {p.stderr[-500:]}")
        return None
    return json.loads(p.stdout.strip().splitlines()[-1]) or None


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
