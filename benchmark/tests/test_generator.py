"""What the generator draws comes from the seed alone."""
import collections
import os

import generator as G

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def traffic(name="mixed-10m"):
    return G.load_traffic(os.path.join(TRAFFIC, name + ".json"))


def test_same_seed_same_schedule_other_seed_another():
    t = traffic()
    a = G.draw_schedule(t, 2147483659, 3, 200)
    assert a == G.draw_schedule(t, 2147483659, 3, 200)
    assert a != G.draw_schedule(t, 2147483660, 3, 200)
    assert a != G.draw_schedule(t, 2147483659, 4, 200)


def test_every_seed_gets_the_same_work_in_another_order():
    t = traffic()
    counts = [collections.Counter(op.kind for op in G.draw_schedule(t, seed, 0, 200))
              for seed in (1, 2, 3000000019)]
    assert counts[0] == counts[1] == counts[2] == {"GET": 90, "STAT": 60, "PUT": 30, "DELETE": 20}
    g1, g2 = G.arrival_gaps(1, 17.6, 0), G.arrival_gaps(2, 17.6, 0)
    assert g1 != g2 and sorted(g1) == sorted(g2)
    assert abs(sum(g1) / len(g1) - 1 / 17.6) < 0.002  # the exponential's mean


def test_kind_block_holds_the_mix_exactly():
    assert collections.Counter(G.kind_block({"GET": 45, "STAT": 30, "PUT": 15, "DELETE": 10})) == {
        "GET": 9, "STAT": 6, "PUT": 3, "DELETE": 2}
    assert G.kind_block({"GET": 100}) == ["GET"]


def test_pool_is_stationary_and_reads_hit_live_keys():
    t = traffic()
    o = G.Owner(7, 0, t)
    o.fill()
    n_keys = len(o.keys)
    deleted = []
    for _ in range(2000):
        op = o.next_op()
        if op.kind == "DELETE":
            deleted.append(op.key)
        elif op.kind == "PUT" and deleted:
            assert op.key == deleted.pop(0)  # a DELETE's key is put back by the next PUT
        else:
            assert op.version >= 1 and op.size == 10485760
        assert n_keys - 4 <= len(o.model.version) <= n_keys  # two blocks' DELETEs back to back


def test_every_key_has_one_owner():
    paced = dict(traffic(), loop="open", clients=64, workers=4, rate_ops_per_s=15.5)
    for t in (traffic(), traffic("get-degraded-10m"), paced):
        keys = [k for o in range(G.n_owners(t)) for k in G.owner_keys(t, o)]
        assert len(keys) == len(set(keys)) == t["pool_objects"]


def test_payload_names_its_key_and_version():
    p = G.Payloads(5, [4096])
    op = G.Op("PUT", "obj-00001", 3, 4096)
    (prefix, tail), sha = p.body(op)
    import hashlib
    whole = prefix + tail
    assert len(whole) == 4096 and hashlib.sha256(whole).hexdigest() == sha
    assert p.matches(op, whole) and whole == p.whole("obj-00001", 3, 4096)
    assert not p.matches(op, whole[:100] + bytes([whole[100] ^ 1]) + whole[101:])
    assert not p.matches(G.Op("GET", "obj-00001", 4, 4096), whole)  # a stale version
