"""A seed's size draws match the traffic file's weights."""
import collections
import os

import generator as G
import pytest

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def traffic(name):
    return G.load_traffic(os.path.join(TRAFFIC, name + ".json"))


@pytest.mark.parametrize("seed", [7, 3100000101])
def test_sixteen_sizes_of_equal_weight_are_drawn_equally(seed):
    t = traffic("mixed-randsize")
    sizes = [s for s, _ in t["sizes"]]
    drawn = collections.Counter()
    for owner in range(G.n_owners(t)):
        o = G.Owner(seed, owner, t)
        drawn.update(op.size for op in o.fill())
        drawn.update(op.size for op in (o.next_op() for _ in range(2000)) if op.kind == "PUT")
    n = sum(drawn.values())
    assert set(drawn) == set(sizes) and n > 6000
    for s in sizes:  # 1/16 each: within a fifth of it over some 6,000 draws
        assert abs(drawn[s] / n - 1 / 16) < 0.2 / 16, (s, drawn[s], n)
    # the fill alone: every process pre-builds its bodies from the same 16
    fill = collections.Counter(op.size for o in range(G.n_owners(t))
                               for op in G.Owner(seed, o, t).fill())
    assert sum(fill.values()) == t["pool_objects"] and set(fill) <= set(sizes)


def test_weights_are_honoured_and_a_seed_repeats_itself():
    t = dict(traffic("mixed-randsize"), sizes=[[1000, 3], [2000, 1]])
    o = G.Owner(5, 0, t)
    draws = [o._size() for _ in range(4000)]
    assert abs(draws.count(1000) / 4000 - 0.75) < 0.03
    again = G.Owner(5, 0, t)
    assert draws == [again._size() for _ in range(4000)]
    assert draws != [G.Owner(6, 0, t)._size() for _ in range(4000)]


def test_a_get_reads_the_size_its_put_wrote():
    t = traffic("mixed-randsize")
    o = G.Owner(11, 3, t)
    written = {op.key: op.size for op in o.fill()}
    for _ in range(500):
        op = o.next_op()
        if op.kind == "PUT":
            written[op.key] = op.size
        elif op.kind in ("GET", "STAT"):
            assert op.size == written[op.key]
