"""The traffic generator: a pure function of (mix, deployment, seed)."""

import collections
import json
import os

import pytest

from benchmark.traffic.mix import Mix, Model

MIXES = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                     "traffic", "mixes")


def load_mix(name):
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)

CFG = {"object_size": 1 << 16,
       "image": {"name": "img", "size": 1 << 20, "order": 16}}
BIG = 2**31 + 12345     # seeds above 32 signed bits


@pytest.mark.parametrize("name", ["rados_write_full_4m_qd16",
                                  "rbd_randrw_4k_r70_qd16"])
def test_same_seed_replays_the_same_ops(name):
    mix = load_mix(name)
    a, b = Mix(mix, CFG, BIG), Mix(mix, CFG, BIG)
    ops = [a.op(i) for i in range(200)]
    assert ops == [b.op(i) for i in range(200)]
    assert [a.data(o) for o in ops[:20] if o.kind != "read"] == \
        [b.data(o) for o in ops[:20] if o.kind != "read"]
    c = Mix(mix, CFG, BIG + 1)
    assert ops != [c.op(i) for i in range(200)]


def test_every_seed_does_the_same_mix_of_work():
    mix = load_mix("rbd_randrw_4k_r70_qd16")
    for seed in (1, 7, BIG):
        m = Mix(mix, CFG, seed)
        kinds = collections.Counter(m.op(i).kind for i in range(1000))
        assert kinds == {"read": 700, "write": 300}
        for i in range(300):
            op = m.op(i)
            assert op.off % 4096 == 0 and op.off + op.length <= 1 << 16


def test_cycle_keys_visit_the_whole_working_set():
    mix = load_mix("rados_write_full_4m_qd16")
    m = Mix(mix, CFG, BIG)
    ws = mix["working_set"]
    first = [m.op(i).oid for i in range(ws)]
    assert len(set(first)) == ws
    assert [m.op(ws + i).oid for i in range(ws)] == first


def test_unknown_key_distribution_is_refused():
    mix = dict(load_mix("rbd_randrw_4k_r70_qd16"),
               keys={"dist": "zipf", "s": 0.99})
    with pytest.raises(ValueError, match="zipf"):
        Mix(mix, CFG, 3)


def test_model_splices_partial_writes_over_whole_objects():
    mix = load_mix("rbd_randrw_4k_r70_qd16")
    m = Mix(mix, CFG, 11)
    model = Model(m)
    full = m.prefill_ops()[0]
    model.ack(full, m.data(full))
    base = m.data(full)
    w = next(o for o in (m.op(i) for i in range(100))
             if o.kind == "write" and o.oid == full.oid)
    model.ack(w, m.data(w))
    want = base[:w.off] + m.data(w) + base[w.off + w.length:]
    assert model.expected(full.oid) == want
    assert model.expected(full.oid, w.off, w.length) == m.data(w)
    assert model.expected("absent") is None


def test_open_loop_arrivals_are_seeded():
    m = Mix(load_mix("rados_write_full_4m_qd16"), CFG, 9)
    a = m.arrivals(10.0, "poisson", 100)
    assert list(a) == list(Mix(load_mix("rados_write_full_4m_qd16"),
                                CFG, 9).arrivals(10.0, "poisson", 100))
    assert a[0] == 0 and all(x < y for x, y in zip(a, a[1:]))
    assert list(m.arrivals(4.0, "fixed", 3)) == [0.0, 0.25, 0.5]
