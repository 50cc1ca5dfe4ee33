"""The load generator: one general reader of a traffic file.

A traffic file (``benchmark/traffic/<name>.json``) gives the loop (closed or
open), the clients or the connection pool, the rate of an open loop, the mix
of GET / STAT / PUT / DELETE in percent, the object sizes, the pool and the
drives that are lost after the fill.  Everything a request is made of - which
operation, which key, which bytes - is drawn from ``--seed``, never from the
clock: every seed gets the same multiset of operations and of inter-arrival
gaps in another order, so two seeds differ in order and not in work.

Clients run in worker processes of their own (``WORKER_CLIENTS`` to an
interpreter), never as threads of the harness.  Payload buffers and the
SHA-256 state over them are made during set-up; a PUT in the window only
appends its 64-byte trailer.
"""

from __future__ import annotations

import collections
import gc
import hashlib
import http.client
import json
import math
import os
import queue
import random
import sys
import threading
import time
from functools import reduce

from reference import TRAILER, StoreModel, trailer
from s3 import Client

WORKER_CLIENTS = 5  # closed-loop clients (or a share of the open loop) per interpreter
PAYLOAD_VARIANTS = 4  # distinct payload bodies per object size
GAP_BLOCK = 200  # inter-arrival gaps are the quantiles of one block, shuffled
BUCKET = "bench"

Op = collections.namedtuple("Op", "kind key version size")
# one request as the generator saw it; times on CLOCK_MONOTONIC, shared by all processes
Record = collections.namedtuple(
    "Record", "owner kind key due start end status failed wrong nbytes")


def load_traffic(path: str) -> dict:
    with open(path) as f:
        t = json.load(f)
    if t["loop"] not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be closed or open")
    return t


def n_workers(traffic: dict) -> int:
    return traffic.get("workers") or max(1, math.ceil(traffic["clients"] / WORKER_CLIENTS))


def n_owners(traffic: dict) -> int:
    """Key spaces: one per closed-loop client, one per worker of an open loop."""
    return traffic["clients"] if traffic["loop"] == "closed" else n_workers(traffic)


def owner_keys(traffic: dict, owner: int) -> "list[str]":
    return [f"obj-{j:05d}" for j in range(traffic["pool_objects"])
            if j % n_owners(traffic) == owner]


def kind_block(mix: "dict[str, int]") -> "list[str]":
    """The shortest run of operations that holds the mix exactly."""
    g = reduce(math.gcd, [w for w in mix.values() if w])
    return [k for k, w in sorted(mix.items()) for _ in range(w // g)]


def arrival_gaps(seed: int, rate: float, block: int) -> "list[float]":
    """One block of Poisson inter-arrival gaps: the exponential's quantiles,
    in an order drawn from the seed."""
    gaps = [-math.log(1.0 - (j + 0.5) / GAP_BLOCK) / rate for j in range(GAP_BLOCK)]
    random.Random(f"{seed}/arrivals/{block}").shuffle(gaps)
    return gaps


class Owner:
    """One key space and the requests drawn on it, in order."""

    def __init__(self, seed: int, index: int, traffic: dict):
        self.index = index
        self.rng = random.Random(f"{seed}/owner/{index}")
        self.keys = owner_keys(traffic, index)
        self.mix = {k: int(w) for k, w in traffic["mix"].items() if w}
        self.sizes = traffic["sizes"]  # [[bytes, weight], ...]
        self.model = StoreModel()
        self.deleted: "collections.deque[str]" = collections.deque()
        self.recent: "collections.deque[str]" = collections.deque(
            maxlen=max(1, min(8, len(self.keys) // 3)))
        self._block: "list[str]" = []

    def _size(self) -> int:
        if len(self.sizes) == 1:
            return int(self.sizes[0][0])
        return int(self.rng.choices([s for s, _ in self.sizes],
                                    [w for _, w in self.sizes])[0])

    def fill(self) -> "list[Op]":
        return [self._put(k) for k in self.keys]

    def _put(self, key: str) -> Op:
        size = self._size()
        return Op("PUT", key, self.model.put(key, size), size)

    def _live_key(self) -> str:
        live = [k for k in self.keys if k in self.model.version]
        fresh = [k for k in live if k not in self.recent]
        return self.rng.choice(fresh or live)

    def next_op(self, kind: "str | None" = None) -> Op:
        if kind is None:
            if not self._block:
                self._block = kind_block(self.mix)
                self.rng.shuffle(self._block)
            kind = self._block.pop()
        if kind == "PUT":
            # a DELETE's key is put back by the next PUT: the pool is stationary
            key = self.deleted.popleft() if self.deleted else self._live_key()
            op = self._put(key)
        else:
            key = self._live_key()
            version, size = self.model.live(key)
            op = Op(kind, key, version, size)
            if kind == "DELETE":
                self.model.delete(key)
                self.deleted.append(key)
        self.recent.append(key)
        return op


def draw_schedule(traffic: dict, seed: int, owner: int, n: int) -> "list[Op]":
    """The first n requests of one owner after the fill (for tests and readers)."""
    o = Owner(seed, owner, traffic)
    o.fill()
    return [o.next_op() for _ in range(n)]


class Payloads:
    """Bodies by (size, variant): the bytes before the trailer, and a SHA-256
    already run over them."""

    def __init__(self, seed: int, sizes: "list[int]"):
        self.prefix: "dict[tuple[int, int], bytes]" = {}
        self.hasher: "dict[tuple[int, int], hashlib._Hash]" = {}
        for size in sizes:
            if size < 2 * TRAILER:
                raise ValueError("objects are at least 128 bytes")
            for v in range(PAYLOAD_VARIANTS):
                body = random.Random(f"{seed}/payload/{size}/{v}").randbytes(size - TRAILER)
                self.prefix[size, v] = body
                self.hasher[size, v] = hashlib.sha256(body)

    @staticmethod
    def variant(key: str, version: int) -> int:
        return (hash_key(key) + version) % PAYLOAD_VARIANTS

    def body(self, op: Op) -> "tuple[tuple[bytes, bytes], str]":
        v = self.variant(op.key, op.version)
        tail = trailer(op.key, op.version)
        h = self.hasher[op.size, v].copy()
        h.update(tail)
        return (self.prefix[op.size, v], tail), h.hexdigest()

    def matches(self, op: Op, got: bytes) -> bool:
        if len(got) != op.size or got[-TRAILER:] != trailer(op.key, op.version):
            return False
        want = self.prefix[op.size, self.variant(op.key, op.version)]
        return memoryview(got)[:-TRAILER] == memoryview(want)

    def whole(self, key: str, version: int, size: int) -> bytes:
        return self.prefix[size, self.variant(key, version)] + trailer(key, version)


def hash_key(key: str) -> int:
    return int.from_bytes(hashlib.md5(key.encode()).digest()[:4], "big")


def execute(client: Client, payloads: Payloads, owner: int, op: Op, due: float) -> Record:
    """Send one request and judge its answer against the reference model."""
    path = f"/{BUCKET}/{op.key}"
    start = time.monotonic()
    status, failed, wrong, nbytes, end = 0, False, False, 0, None
    try:
        if op.kind == "PUT":
            body, sha = payloads.body(op)
            r = client.request("PUT", path, body=body, body_sha256=sha)
            wrong = r.status != 200 and r.status < 500
            nbytes = op.size
        elif op.kind == "GET":
            r = client.request("GET", path)
            end = time.monotonic()
            wrong = r.status < 500 and not (r.status == 200 and payloads.matches(op, r.body))
            nbytes = op.size
        elif op.kind == "STAT":
            r = client.request("HEAD", path)
            wrong = r.status < 500 and not (
                r.status == 200 and int(r.headers.get("content-length", -1)) == op.size)
        elif op.kind == "DELETE":
            r = client.request("DELETE", path)
            wrong = r.status != 204 and r.status < 500
        else:
            raise ValueError(f"unknown operation {op.kind}")
        status = r.status
        failed = status >= 500
    except (OSError, http.client.HTTPException) as e:  # the answer never came
        failed = True
        status = -1
        print(f"generator: {op.kind} {op.key}: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
    if end is None:
        end = time.monotonic()
    if failed or wrong:
        nbytes = 0
    return Record(owner, op.kind, op.key, due, start, end, status, failed, wrong, nbytes)


class Worker:
    """One generator process: its owners, its lanes (a thread and a connection
    each), and the phases the harness asks for."""

    def __init__(self, conn, spec: dict):
        self.conn = conn
        self.send_lock = threading.Lock()
        self.spec = spec
        self.traffic = spec["traffic"]
        self.seed = spec["seed"]
        self.closed = self.traffic["loop"] == "closed"
        self.owners = [Owner(self.seed, i, self.traffic) for i in spec["owners"]]
        self.lanes = spec["lanes"]
        host, port = spec["host"], spec["port"]
        self.clients = [Client(host, port) for _ in range(self.lanes)]
        self.payloads = Payloads(self.seed, sorted({int(s) for s, _ in self.traffic["sizes"]}))
        self.stop_at = math.inf
        # requests on one key are sent in the order they were drawn
        self.key_drawn: "dict[str, int]" = collections.defaultdict(int)
        self.key_sent: "dict[str, int]" = collections.defaultdict(int)
        self.key_turn = threading.Condition()
        self.tainted: "set[str]" = set()
        self.marks: "dict[str, tuple[float, float]]" = {}

    # -- helpers ---------------------------------------------------------

    def _send(self, msg) -> None:
        with self.send_lock:
            self.conn.send(msg)

    def _ticket(self, op: Op) -> int:
        t = self.key_drawn[op.key]
        self.key_drawn[op.key] = t + 1
        return t

    def _in_turn(self, lane: int, owner: int, op: Op, ticket: int, due: float) -> Record:
        with self.key_turn:
            while self.key_sent[op.key] != ticket:
                self.key_turn.wait()
        try:
            rec = execute(self.clients[lane], self.payloads, owner, op, due)
            if op.key in self.tainted:  # its state is unknown since a write on it failed
                rec = rec._replace(wrong=False, nbytes=0)
            elif rec.failed and op.kind in ("PUT", "DELETE"):
                self.tainted.add(op.key)
            return rec
        finally:
            with self.key_turn:
                self.key_sent[op.key] = ticket + 1
                self.key_turn.notify_all()

    def _run_lanes(self, target, n: int) -> "list[Record]":
        out: "list[list[Record]]" = [[] for _ in range(n)]
        threads = [threading.Thread(target=target, args=(i, out[i])) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return [r for lane in out for r in lane]

    def _pool(self, work: "list[tuple[int, Op, float]]") -> "list[Record]":
        """Execute (owner position, op, due) items from all lanes at once."""
        q: "queue.SimpleQueue" = queue.SimpleQueue()
        for pos, op, due in work:
            q.put((pos, op, due, self._ticket(op)))

        def lane(i: int, out: "list[Record]") -> None:
            while True:
                try:
                    pos, op, due, ticket = q.get_nowait()
                except queue.Empty:
                    return
                self._wait_until(due)
                out.append(self._in_turn(i, self.owners[pos].index, op, ticket, due))

        return self._run_lanes(lane, self.lanes)

    @staticmethod
    def _wait_until(t: float) -> None:
        while True:
            d = t - time.monotonic()
            if d <= 0:
                return
            time.sleep(min(d, 0.05))

    # -- phases ----------------------------------------------------------

    def fill(self) -> "list[Record]":
        now = time.monotonic()
        return self._pool([(p, op, now) for p, o in enumerate(self.owners) for op in o.fill()])

    def burst(self, kind: str, active: int, rounds: int, start_at: float) -> "list[Record]":
        """Lanes whose global number is below ``active`` each send ``rounds``
        requests of one kind, starting together."""
        mine = [g for g in self.spec["lane_numbers"] if g < active]
        work = []
        for r in range(rounds):
            for j in range(len(mine)):
                pos = j % len(self.owners)
                work.append((pos, self.owners[pos].next_op(kind), start_at + 0.5 * r))
        return self._pool(work)

    def run(self, start_at: float) -> "list[Record]":
        return self._run_closed(start_at) if self.closed else self._run_open(start_at)

    def _run_closed(self, start_at: float) -> "list[Record]":
        def lane(i: int, out: "list[Record]") -> None:
            owner = self.owners[i]
            self._wait_until(start_at)
            while True:
                now = time.monotonic()
                if now >= self.stop_at:
                    return
                op = owner.next_op()
                out.append(self._in_turn(i, owner.index, op, self._ticket(op), now))

        return self._run_lanes(lane, len(self.owners))

    def _run_open(self, start_at: float) -> "list[Record]":
        """Arrival number i of the whole cell is this worker's when
        i % workers == worker; it is due at start_at plus the gaps so far."""
        w, nw, rate = self.spec["worker"], self.spec["workers"], self.traffic["rate_ops_per_s"]
        q: "queue.SimpleQueue" = queue.SimpleQueue()
        owner = self.owners[0]

        def dispatch() -> None:
            due, i, block = start_at, 0, 0
            while True:
                for gap in arrival_gaps(self.seed, rate, block):
                    due += gap
                    mine = i % nw == w
                    i += 1
                    if not mine:
                        continue
                    if due < self.stop_at:
                        self._wait_until(due)
                    if due >= self.stop_at:  # may have been set while waiting
                        for _ in range(self.lanes):
                            q.put(None)
                        return
                    op = owner.next_op()
                    q.put((op, due, self._ticket(op)))
                block += 1

        def lane(i: int, out: "list[Record]") -> None:
            while True:
                item = q.get()
                if item is None:
                    return
                op, due, ticket = item
                out.append(self._in_turn(i, owner.index, op, ticket, due))

        d = threading.Thread(target=dispatch)
        d.start()
        records = self._run_lanes(lane, self.lanes)
        d.join()
        return records

    # -- command loop ----------------------------------------------------

    def serve(self) -> None:
        running: "threading.Thread | None" = None
        while True:
            msg = self.conn.recv()
            cmd = msg[0]
            if cmd == "quit":
                if running is not None:
                    running.join()
                for c in self.clients:
                    c.close()
                return
            if cmd == "stop_at":
                self.stop_at = msg[1]
            elif cmd == "mark":
                self.marks[msg[1]] = (time.monotonic(), time.process_time())
            elif cmd == "freeze":
                gc.collect()
                gc.freeze()
                gc.disable()
                self._send(("frozen",))
            elif cmd == "run":
                running = threading.Thread(target=self._phase, args=(self.run, msg[1:]))
                running.start()
            elif cmd in ("fill", "burst"):
                self._phase(getattr(self, cmd), msg[1:])
            else:
                raise ValueError(f"unknown command {cmd}")

    def _phase(self, fn, args) -> None:
        records = fn(*args)
        if "open" in self.marks:
            self.marks.setdefault("close", (time.monotonic(), time.process_time()))
        live = {k: (o.model.version[k], o.model.size[k])
                for o in self.owners for k in o.model.version}
        self._send(("done", records, dict(self.marks), live,
                    sum(c.reconnects for c in self.clients)))


def worker_main(conn, spec: dict) -> None:
    os.sched_setaffinity(0, spec["cores"])
    Worker(conn, spec).serve()
