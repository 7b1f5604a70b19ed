"""The generator: every seed gets the same prompt lengths, output
lengths and greedy flags, block by block, in another order."""
import os

import pytest

import spec
import traffic

MIXES = ["chat_poisson", "batch_sampled", "batch_greedy"]


def load(mix):
    return spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                       mix + ".json"))


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_the_work(mix):
    t = load(mix)
    m = traffic.STRATUM
    sa, sb = traffic.Stream(t, 1, 1000), traffic.Stream(t, 2 ** 31 + 9, 1000)
    ra = [sa.take() for _ in range(2 * m)]
    rb = [sb.take() for _ in range(2 * m)]
    for key in (lambda r: len(r.prompt), lambda r: r.n_new,
                lambda r: r.greedy):
        assert sorted(map(key, ra)) == sorted(map(key, rb))
    assert [len(r.prompt) for r in ra] != [len(r.prompt) for r in rb]
    assert any((x.prompt[:4] != y.prompt[:4]).any() for x, y in zip(ra, rb))
    assert ra[0].rid == 0 and ra[-1].rid == 2 * m - 1


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_on_the_grid_and_capped(mix):
    t = load(mix)
    s = traffic.Stream(t, 3, 1000)
    reqs = [s.take() for _ in range(traffic.STRATUM)]
    assert {len(r.prompt) for r in reqs} <= set(t["prompt"]["grid"])
    assert set(traffic.lengths_used(t)) == {len(r.prompt) for r in reqs}
    assert all(t["output"]["min"] <= r.n_new <= t["output"]["cap"]
               for r in reqs)
    share = sum(r.greedy for r in reqs) / len(reqs)
    assert share == pytest.approx(t["greedy_share"], abs=1 / len(reqs))


def test_open_loop_rate():
    t = load("chat_poisson")
    rate = t["arrivals"]["rate_per_s"]
    s = traffic.Stream(t, 4, 1000)
    reqs = [s.take() for _ in range(4 * traffic.STRATUM)]
    assert reqs[-1].arrival == pytest.approx(len(reqs) / rate, rel=0.05)
    assert all(b.arrival > a.arrival for a, b in zip(reqs, reqs[1:]))
