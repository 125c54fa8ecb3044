"""The traffic generator: seeded, the same work for every seed, prompts on
their buckets, open-loop due times inside the window."""

from collections import Counter

import numpy as np
import pytest

import loadgen
import registry
from conftest import TINY_CLOSED, TINY_OPEN

MIXES = sorted(p.stem for p in (registry.BENCH_DIR / "traffic").glob("*.json"))


def _sizes(reqs):
    return Counter((r.prompt_len, r.max_new) for r in reqs)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    m = registry.load_traffic(mix)
    a = loadgen.Traffic(m, 2**40 + 3, 30, 32768)
    b = loadgen.Traffic(m, 2**40 + 3, 30, 32768)
    ra = a.pool if a.open else [a.next_closed() for _ in range(40)]
    rb = b.pool if b.open else [b.next_closed() for _ in range(40)]
    assert [(r.prompt_len, r.max_new, r.due) for r in ra] == \
        [(r.prompt_len, r.max_new, r.due) for r in rb]
    assert all((x.tokens == y.tokens).all() for x, y in zip(ra, rb))


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_the_work_in_another_order(mix):
    m = registry.load_traffic(mix)
    a = loadgen.Traffic(m, 1, 30, 32768)
    b = loadgen.Traffic(m, 2, 30, 32768)
    n = len(a.pool) if a.open else 2 * m.get("block", 16)
    ra = a.pool if a.open else [a.next_closed() for _ in range(n)]
    rb = b.pool if b.open else [b.next_closed() for _ in range(n)]
    assert _sizes(ra) == _sizes(rb)
    assert [(r.prompt_len, r.max_new) for r in ra] != [(r.prompt_len, r.max_new) for r in rb]
    assert _sizes(a.steady_state(6)) == _sizes(b.steady_state(6))


@pytest.mark.parametrize("mix", MIXES)
def test_prompts_fall_on_buckets_and_fit_the_cache(mix):
    m = registry.load_traffic(mix)
    t = loadgen.Traffic(m, 9, 30, 1000)
    buckets = set(m["prompt"]["buckets"])
    reqs = t.pool if t.open else [t.next_closed() for _ in range(64)]
    assert {r.prompt_len for r in reqs} <= buckets
    assert [r.prompt_len for r in t.warmup()] == sorted(buckets)
    for r in reqs + t.steady_state(8):
        assert len(r.tokens) == r.prompt_len
        assert 0 < r.tokens.min() and r.tokens.max() < 1000
        assert r.prompt_len + r.max_new < m["capacity"]


def test_open_loop_due_times_fill_the_window():
    t = loadgen.Traffic(TINY_OPEN, 4, 10.0, 512)
    due = np.array([r.due for r in t.pool])
    assert len(due) == round(TINY_OPEN["rate_per_s"] * 10.0)
    assert 0 <= due.min() and due.max() <= 10.0
    gaps = np.diff(np.sort(due))
    assert gaps.min() > 0
    # Poisson: inter-arrival gaps spread like an exponential (cv near 1)
    assert 0.6 < gaps.std() / gaps.mean() < 1.4


def test_closed_loop_blocks_hold_the_whole_mix():
    t = loadgen.Traffic(dict(TINY_CLOSED, block=4), 4, 10.0, 512)
    reqs = [t.next_closed() for _ in range(12)]
    assert len({r.rid for r in reqs}) == 12
    blocks = [_sizes(reqs[i:i + 4]) for i in (0, 4, 8)]
    assert blocks[0] == blocks[1] == blocks[2]


def test_quantiles_and_buckets():
    q = loadgen.quantiles({"dist": "lognormal", "median": 512, "sigma": 0.8,
                           "min": 64, "max": 1536}, 1001)
    assert q[500] == 512 or np.median(q) == 512
    assert q.min() >= 64 and q.max() <= 1536
    assert list(loadgen.quantiles({"dist": "fixed", "value": 4}, 3)) == [4, 4, 4]
    assert loadgen.bucket(129, [128, 256]) == 256
    with pytest.raises(ValueError):
        loadgen.bucket(300, [128, 256])
