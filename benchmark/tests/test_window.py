"""The window refuses to open while the compile counter still moves; rates are
all the work over all the window; open-loop tails count from the due time."""
import types

import generator as G
import readers
import run as harness


class FakeTime:
    def __init__(self):
        self.now = 0.0

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += s


def test_window_waits_for_the_compile_counter():
    t = FakeTime()
    # the counter moves until t = 20 s, then stands still
    opened, waited = harness.wait_quiet(lambda: min(int(t.now), 20), quiet_s=6, cap_s=90,
                                        min_s=10, clock=t.clock, sleep=t.sleep)
    assert opened and 26.0 <= waited <= 27.0


def test_window_opens_at_the_cap_and_says_so():
    t = FakeTime()
    opened, waited = harness.wait_quiet(lambda: int(t.now), quiet_s=6, cap_s=30, min_s=10,
                                        clock=t.clock, sleep=t.sleep)
    assert not opened and waited >= 30


def test_quiet_counter_still_waits_the_minimum():
    t = FakeTime()
    opened, waited = harness.wait_quiet(lambda: 5, quiet_s=6, cap_s=90, min_s=10,
                                        clock=t.clock, sleep=t.sleep)
    assert opened and 10.0 <= waited <= 10.5


def rec(kind, due, start, end, nbytes=0, failed=False, wrong=False, status=200):
    return G.Record(0, kind, "k", due, start, end, status, failed, wrong, nbytes)


def a_run(records, loop="closed", t0=100.0, t1=110.0):
    return types.SimpleNamespace(records=records, t0=t0, t1=t1, traffic={"loop": loop})


def test_rates_are_all_work_over_all_the_window():
    mib = 1 << 20
    r = a_run([
        rec("GET", 100, 100, 101, 10 * mib),
        rec("PUT", 99, 99, 100.5, 10 * mib),      # began before the window, ended inside
        rec("GET", 109, 109, 110.5, 10 * mib),    # ended after the close: not this window's
        rec("STAT", 105, 105, 105.1),
        rec("GET", 106, 106, 107, 0, failed=True, status=503),
    ])
    # 20 MiB and 3 requests completed in a 10 s window, however long the server was idle
    assert readers.payload_rate(r) == 2.0
    assert readers.op_rate(r) == 0.3
    assert readers.shed_share(r) == 25.0


def test_a_failure_misses_every_tail():
    ok = [rec("GET", 100 + i * 0.1, 100 + i * 0.1, 100.2 + i * 0.1) for i in range(19)]
    r = a_run(ok + [rec("GET", 105, 105, 105.1, failed=True, status=503)], loop="open")
    assert readers.open_tail(r, "GET", 95) == 200.00000000000284 or \
        abs(readers.open_tail(r, "GET", 95) - 200.0) < 1e-6
    assert readers.open_tail(r, "GET", 100) == readers.MISSED_MS
    assert readers.open_tail(a_run(ok), "GET", 95) is None  # a closed loop has no such tail


def test_open_loop_latency_counts_from_the_due_time():
    # sent 0.4 s late (the generator waited for a connection), answered in 0.1 s
    r = a_run([rec("GET", 101.0, 101.4, 101.5)], loop="open")
    assert abs(readers.open_tail(r, "GET", 95) - 500.0) < 1e-6
    assert abs(readers.gen_late(r) - 400.0) < 1e-6
    assert readers.backlog_end(a_run([rec("GET", 109.9, 109.9, 110.3)], loop="open")) == 1.0
