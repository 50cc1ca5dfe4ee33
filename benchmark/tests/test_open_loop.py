"""An open loop against a server that stalls: the requests that were due
during the stall are timed from when they were due, not from when a free
connection could send them."""
import http.server
import threading
import time

import generator as G
import readers
import types


class Stalling(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    store: dict = {}
    stall_until = 0.0

    def log_message(self, *a):
        pass

    def _answer(self, code, body=b""):
        d = type(self).stall_until - time.monotonic()
        if d > 0:
            time.sleep(d)
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        if self.command != "HEAD":
            self.wfile.write(body)

    def do_PUT(self):
        self.store[self.path] = self.rfile.read(int(self.headers["Content-Length"]))
        self._answer(200)

    def do_GET(self):
        body = self.store.get(self.path)
        self._answer(200 if body is not None else 404, body or b"")

    def do_HEAD(self):
        body = self.store.get(self.path)
        self.send_response(200 if body is not None else 404)
        self.send_header("Content-Length", str(len(body or b"")))
        self.end_headers()

    def do_DELETE(self):
        self.store.pop(self.path, None)
        self._answer(204)


def test_stall_shows_in_latency_from_due():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Stalling)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        traffic = {"loop": "open", "clients": 1, "workers": 1, "rate_ops_per_s": 40.0,
                   "mix": {"GET": 100}, "sizes": [[256, 1]], "pool_objects": 8}
        spec = {"traffic": traffic, "seed": 3, "host": "127.0.0.1", "port": srv.server_address[1],
                "owners": [0], "lanes": 1, "lane_numbers": [0], "worker": 0, "workers": 1,
                "cores": []}
        w = G.Worker(None, spec)
        assert not [r for r in w.fill() if r.failed or r.wrong]
        t0 = time.monotonic() + 0.1
        Stalling.stall_until = t0 + 1.0  # every answer waits until one second into the run
        w.stop_at = t0 + 2.0
        records = w._run_open(t0)
        assert len(records) > 50 and not [r for r in records if r.failed or r.wrong]
        early = [r for r in records if r.due < t0 + 0.5]
        # one connection: the first request holds it for the stall; those due meanwhile
        # leave late, and their latency from the due time holds the wait
        assert min(r.end - r.due for r in early) > 0.45
        assert max(r.start - r.due for r in early) > 0.4
        assert max(r.end - r.start for r in early[1:]) < 0.3
        run = types.SimpleNamespace(records=records, t0=t0, t1=t0 + 2.0, traffic=traffic)
        assert readers.open_tail(run, "GET", 95) > 450.0
        assert readers.gen_late(run) > 300.0
    finally:
        srv.shutdown()
        srv.server_close()
