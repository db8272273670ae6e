"""The generators: deterministic in --seed, inside their clips, and the
same work for every seed."""

import numpy as np

from benchmark import harness, traffic, weights

BIG = 2**31 + 12345


def mix(name):
    return harness.load_json(harness.HERE, "traffic", name + ".json")


def test_chat_is_deterministic_and_clipped():
    m = mix("chat-steady")
    a = traffic.serve_requests(m, BIG, 40, 65024)
    b = traffic.serve_requests(m, BIG, 40, 65024)
    assert a == b
    assert len(a) == int(m["arrivals"]["rate_per_s"] * 40)
    for r in a:
        assert 32 <= len(r["prompt"]) <= 1536 and 16 <= r["out"] <= 448
        assert len(r["prompt"]) + r["out"] <= 2048
        assert 0 <= r["due"] < 40
        assert min(r["prompt"]) >= 0 and max(r["prompt"]) < 65024
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)


def test_a_fixed_schedule_leaves_the_seed_the_tokens():
    m = mix("chat-steady")
    a = traffic.serve_requests(m, 1, 50, 65024)
    b = traffic.serve_requests(m, BIG, 50, 65024)
    assert [(r["due"], len(r["prompt"]), r["out"]) for r in a] == \
        [(r["due"], len(r["prompt"]), r["out"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_every_seed_offers_the_same_work():
    m = dict(mix("chat-steady"),
             arrivals={"process": "poisson", "rate_per_s": 3.0})
    del m["order_seed"]
    a = traffic.serve_requests(m, 1, 40, 65024)
    b = traffic.serve_requests(m, BIG, 40, 65024)
    assert a != b
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert sorted(r["out"] for r in a) == sorted(r["out"] for r in b)
    gaps = lambda rs: sorted(np.round(np.diff([r["due"] for r in rs]), 9))
    assert np.allclose(sorted(np.diff([r["due"] for r in a])),
                       sorted(np.diff([r["due"] for r in b])), atol=1e-6) \
        or len(gaps(a)) == len(gaps(b))


def test_backlog_cycles_keep_the_distribution():
    m = mix("offline-batch")
    a = traffic.serve_requests(m, 7, 40, 65024, n=600)
    assert len(a) == 600 and all(r["due"] == 0 for r in a)
    first, second = a[:256], a[256:512]
    assert sorted(len(r["prompt"]) for r in first) == \
        sorted(len(r["prompt"]) for r in second)
    mean = np.mean([len(r["prompt"]) for r in a])
    assert 450 < mean < 550


def test_train_batches():
    m = mix("pretrain-2k")
    a = traffic.train_batches(m, BIG, 5, 65024)
    assert a.shape == (5, 1, 2, 2049) and a.dtype == np.int32
    assert (a == traffic.train_batches(m, BIG, 5, 65024)).all()
    assert not (a[0, 0, 0] == a[0, 0, 1]).all()  # rows all differ
    assert a.min() >= 0 and a.max() < 65024


def test_weights_stacked_equals_per_layer_and_takes_big_seeds():
    cfg = harness.load_json(harness.HERE, "tests", "tiny", "configs",
                            "tiny-falcon40.json")
    (stacked,) = weights.make_stacked(cfg, BIG, 2).values()  # one kind
    for i in range(2):
        one = weights.make_layer(cfg, BIG, i)
        for k in one:
            assert np.array_equal(np.asarray(stacked[k][i]),
                                  np.asarray(one[k])), k
    other = weights.make_layer(cfg, BIG + 1, 0)
    assert not np.array_equal(np.asarray(other["wqkv"]),
                              np.asarray(weights.make_layer(cfg, BIG, 0)
                                         ["wqkv"]))
    assert set(one) == {"ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias",
                        "wqkv", "wo", "w1", "w2"}
