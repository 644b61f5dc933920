"""The generator: fixed multisets of sizes per seed, reproducible draws."""
import numpy as np

from bench import traffic

CHAT = {"job": "serve", "rate_rps": 5.0,
        "prompt": {"median": 192, "sigma": 0.9, "min": 16, "max": 1024},
        "output": {"median": 48, "sigma": 0.7, "min": 8, "max": 256}}


def test_seed32_fits_and_spreads():
    big = 2 ** 31 + 12345
    a, b = traffic.seed32(big), traffic.seed32(big + 1)
    assert 0 <= a < 2 ** 31 and 0 <= b < 2 ** 31 and a != b
    assert traffic.seed32(big) == a


def test_lognormal_quantiles():
    x = traffic.lognormal_lengths(CHAT["prompt"], 1001)
    assert x.min() >= 16 and x.max() <= 1024
    assert np.median(x) == 192
    assert np.all(np.diff(x) >= 0)


def test_every_span_holds_the_same_work_in_another_order():
    spans = [4.0, 20.0, 1.0]
    a = traffic.chat_requests(CHAT, 50304, spans, 11)
    b = traffic.chat_requests(CHAT, 50304, spans, 2 ** 33 + 7)
    assert len(a) == len(b) == 20 + 100 + 5

    def window(reqs):
        return [r for r in reqs if 4.0 <= r[0] < 24.0]
    wa, wb = window(a), window(b)
    assert len(wa) == len(wb) == 100
    assert sorted(len(r[1]) for r in wa) == sorted(len(r[1]) for r in wb)
    assert sorted(r[2] for r in wa) == sorted(r[2] for r in wb)
    assert [len(r[1]) for r in wa] != [len(r[1]) for r in wb]
    offs = [r[0] for r in a]
    assert offs == sorted(offs) and offs[0] == 0.0 and offs[-1] < 25.0


def test_a_fixed_order_sends_one_schedule_for_every_seed():
    fixed = dict(CHAT, order_seed=3)
    spans = [4.0, 20.0, 1.0]
    a = traffic.chat_requests(fixed, 50304, spans, 11)
    b = traffic.chat_requests(fixed, 50304, spans, 2 ** 33 + 7)
    assert [(r[0], len(r[1]), r[2]) for r in a] == \
        [(r[0], len(r[1]), r[2]) for r in b]
    assert any(not np.array_equal(x[1], y[1]) for x, y in zip(a, b))
    shuffled = traffic.chat_requests(CHAT, 50304, spans, 11)
    assert sorted(len(r[1]) for r in a) == sorted(len(r[1]) for r in shuffled)


def test_requests_reproduce_from_the_seed():
    a = traffic.chat_requests(CHAT, 100, [1.0, 3.0], 5)
    b = traffic.chat_requests(CHAT, 100, [1.0, 3.0], 5)
    assert all(x[0] == y[0] and x[2] == y[2] and np.array_equal(x[1], y[1])
               for x, y in zip(a, b))
    assert all(((r[1] >= 0) & (r[1] < 100)).all() for r in a)


def test_token_ring_rows_differ_and_targets_shift():
    tr = {"local_batch": 3, "seq_len": 8, "ring": 4}
    ring = traffic.token_ring(tr, 2, 2, 1000, 9)
    assert len(ring) == 4 and ring[0]["tokens"].shape == (2, 2, 3, 8)
    rows = np.concatenate([r["tokens"].reshape(-1, 8) for r in ring])
    assert len({row.tobytes() for row in rows}) == len(rows)
    np.testing.assert_array_equal(ring[1]["tokens"][..., 1:],
                                  ring[1]["targets"][..., :-1])
