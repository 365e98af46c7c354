"""The op sequence and the bodies repeat for a seed and differ across seeds."""

import json
import os

from harness import loader, traffic


def plan_ops(t, seed, c, n):
    p = traffic.ClientPlan(t, seed, c)
    return [p.next_op() for _ in range(n)]


def mixed():
    return traffic.validate(loader.load_json(os.path.join(loader.BENCH_DIR, "traffic", "small-mixed.json")))


def test_same_seed_same_work():
    t = mixed()
    assert plan_ops(t, 2**31 + 5, 3, 500) == plan_ops(t, 2**31 + 5, 3, 500)
    assert traffic.body(2**31 + 5, 9, 4096) == traffic.body(2**31 + 5, 9, 4096)


def test_other_seed_other_order_same_amounts():
    t = mixed()
    a, b = plan_ops(t, 1, 0, 400), plan_ops(t, 2, 0, 400)
    assert a != b
    count = lambda ops: sorted((op, sum(1 for o in ops if o[0] == op)) for op in traffic.OPS)
    assert count(a) == count(b) == [("DELETE", 40), ("GET", 180), ("PUT", 60), ("STAT", 120)]
    assert traffic.body(1, 0, 4096) != traffic.body(2, 0, 4096)


def test_bodies_are_distinct_and_clients_never_share_a_key():
    t = mixed()
    assert len({traffic.body(5, i, 256) for i in range(200)}) == 200
    keys = [{o[1] for o in plan_ops(t, 5, c, 300)} for c in range(t["clients"])]
    for i, a in enumerate(keys):
        for b in keys[i + 1:]:
            assert not a & b
    bids = [o[2] for c in range(t["clients"]) for o in plan_ops(t, 5, c, 300) if o[0] == "PUT"]
    assert len(bids) == len(set(bids))


def test_deleted_key_is_never_used_again():
    t = mixed()
    gone = set()
    for op, key, _bid, _front in plan_ops(t, 11, 4, 2000):
        assert key not in gone
        if op == "DELETE":
            gone.add(key)


def test_read_only_mix_reads_every_preloaded_key():
    t = traffic.validate(loader.load_json(os.path.join(loader.BENCH_DIR, "traffic", "node-loss.json")))
    seen = {o[1] for o in plan_ops(t, 3, 0, 2000)}
    assert seen == {traffic.preload_key(i) for i in range(t["preload_objects"])}
    assert {o[3] for o in plan_ops(t, 3, 0, 10)} == {0, 1}  # both frontends


def test_sample_is_drawn_from_the_seed():
    items = list(range(100))
    assert traffic.sample(7, 1, items, 10, always=[99]) == traffic.sample(7, 1, items, 10, always=[99])
    assert traffic.sample(7, 1, items, 10) != traffic.sample(8, 1, items, 10)
    assert 99 in traffic.sample(7, 1, items, 10, always=[99])
    assert json.dumps(traffic.sample(7, 1, items, 200))  # more asked than there is: all of them
