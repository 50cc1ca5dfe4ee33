"""The generator on the traffic file of `mixed-64m`: five keys a client are
enough.  (A file of its own: a PR that adds a cell may add files to the
benchmark and edit none.)"""
import os

import generator as G

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def traffic(name):
    return G.load_traffic(os.path.join(TRAFFIC, name + ".json"))


def test_eight_clients_over_forty_keys_never_run_out_of_live_keys():
    """`mixed-64m`: five keys a client.  Four DELETEs can stand unreplaced across
    two blocks of the mix (a block is 9 GET, 6 STAT, 3 PUT, 2 DELETE, shuffled:
    two DELETEs at one block's end and two at the next one's start before any
    PUT), so the least live count is 1 and no request ever lacks a key."""
    t = traffic("mixed-64m")
    assert (t["clients"], t["pool_objects"], t["sizes"]) == (8, 40, [[67108864, 1]])
    assert G.n_workers(t) == 2 and G.n_owners(t) == 8 and "workers" not in t
    assert all(len(G.owner_keys(t, o)) == 5 for o in range(8))
    least = 5
    for seed in range(300):
        o = G.Owner(3300000000 + seed, seed % 8, t)
        o.fill()
        for _ in range(400):
            op = o.next_op()  # raises where no live key is left
            assert op.size == 67108864
            least = min(least, len(o.model.version))
    assert least == 1
