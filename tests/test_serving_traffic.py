"""Serving-engine correctness under replayed traffic (ISSUE 8 satellites).

Model-backed engine tests: the `greedy` flag actually selecting the
sampler, prompt-capacity validation at the cap-1/cap/cap+1 boundary, the
frontend honoring admit()'s verdict (rejections surfaced, never silently
dropped), a deterministic seeded arrival trace through a 2-replica
frontend, and the served construction (the compiled WS step behind the
frontend) giving the dense step's token streams.

Everything runs the llama smoke config (tiny f32 dense GQA) so the decode
launches stay interpret-mode cheap.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs import get_config  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.serving.engine import (  # noqa: E402
    ContinuousBatcher,
    Request,
    WorkStealingFrontend,
)

CFG = get_config("llama3.2-3b", smoke=True)
PARAMS = init_params(jax.random.PRNGKey(0), CFG)


def _batcher(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("capacity", 16)
    return ContinuousBatcher(PARAMS, CFG, **kw)


# ---------------------------------------------------------------------------
# satellite 1: the greedy flag must select the sampler


def test_greedy_flag_selects_argmax():
    b = _batcher(greedy=True)
    logits = np.array([[0.0, 3.0, 1.0], [2.0, 0.0, 0.5]], np.float32)
    np.testing.assert_array_equal(b._select(logits), [1, 0])


def test_greedy_false_samples_with_seed():
    """greedy=False must actually sample (the flag used to be stored and
    ignored): over a flat distribution the choices cannot all equal the
    argmax, and the same sample_seed reproduces the same stream."""
    logits = np.zeros((1, 50), np.float32)
    logits[0, 7] += 1e-3  # argmax is 7, but the distribution is ~uniform
    b1 = _batcher(greedy=False, temperature=1.0, sample_seed=123)
    b2 = _batcher(greedy=False, temperature=1.0, sample_seed=123)
    s1 = [int(b1._select(logits)[0]) for _ in range(16)]
    s2 = [int(b2._select(logits)[0]) for _ in range(16)]
    assert s1 == s2, "same seed must reproduce the same sampled stream"
    assert any(t != 7 for t in s1), "greedy=False still argmaxing"
    b3 = _batcher(greedy=False, temperature=1.0, sample_seed=999)
    assert [int(b3._select(logits)[0]) for _ in range(16)] != s1


def test_greedy_sampled_streams_diverge_in_generation():
    """End to end: the same prompt decoded greedy vs sampled (hot
    temperature) produces different continuations — the flag reaches the
    token choice, not just the constructor."""
    prompt = np.array([5, 6, 7], np.int32)
    r_g = Request(0, prompt, max_new=4)
    b_g = _batcher(greedy=True)
    b_g.admit(r_g)
    while b_g.n_live:
        b_g.step()
    r_s = Request(0, prompt, max_new=4)
    b_s = _batcher(greedy=False, temperature=8.0, sample_seed=7)
    b_s.admit(r_s)
    while b_s.n_live:
        b_s.step()
    assert len(r_g.out) == len(r_s.out) == 4
    assert r_g.out != r_s.out, "hot sampling reproduced the greedy stream"


# ---------------------------------------------------------------------------
# satellite 2: admission capacity validation at the boundary


@pytest.mark.parametrize("path", [
    pytest.param({}, id="eager-ws"),
    pytest.param({"jit_ws": True}, id="jit-ws"),
    pytest.param({"use_ws": False}, id="dense"),
])
def test_admit_capacity_boundary(path):
    """cap-1 is the longest admissible prompt (the splice needs len rows
    plus one for the first generated token); len == cap used to corrupt
    the cache splice, len == 0 to admit an empty prompt.  Every decode
    path of the batcher shares the check."""
    cap = 8
    b = _batcher(capacity=cap, **path)
    assert not b.admit(Request(1, np.arange(cap, dtype=np.int32)))      # == cap
    assert not b.admit(Request(2, np.arange(cap + 1, dtype=np.int32)))  # cap+1
    assert not b.admit(Request(3, np.zeros(0, np.int32)))               # empty
    assert b.n_live == 0, "rejected prompts must not occupy a slot"
    assert b.admit(Request(4, np.arange(1, cap, dtype=np.int32)))       # cap-1
    assert b.n_live == 1


# ---------------------------------------------------------------------------
# satellite 3: the frontend honors admit()'s verdict


def test_frontend_surfaces_rejections():
    cap = 8
    fe = WorkStealingFrontend(
        lambda: _batcher(capacity=cap), n_replicas=2
    )
    fe.submit(0, Request(0, np.array([1, 2, 3], np.int32), max_new=2))
    fe.submit(0, Request(1, np.arange(cap, dtype=np.int32), max_new=2))  # too long
    fe.submit(1, Request(2, np.array([4, 5], np.int32), max_new=2))
    completed = fe.run(max_iters=100)
    assert set(completed) == {0, 2}
    assert set(fe.rejected) == {1}, "over-capacity prompt must be surfaced"
    stats = fe.stats()
    assert stats["totals"]["rejected"] == 1
    assert stats["totals"]["admitted"] == 2
    # admitted counter counts only successful admissions: completions and
    # admissions reconcile exactly (no duplicates in a drained serial run)
    assert len(completed) == (
        stats["totals"]["admitted"] - stats["totals"]["dup_completed"]
    )


# ---------------------------------------------------------------------------
# satellite 4: deterministic seeded arrival trace, 2 replicas


def test_seeded_trace_replay_deterministic():
    """Replay a seeded bursty arrival trace twice through fresh 2-replica
    frontends: every submitted rid lands in completed or rejected exactly
    once, counters reconcile, stats() agree with the observable outcome,
    and the whole outcome (streams included) is reproducible."""
    from benchmarks.serving_traffic import make_trace, replay

    n_requests, cap = 4, 16

    def one_run():
        fe = WorkStealingFrontend(
            lambda: _batcher(capacity=cap), n_replicas=2
        )
        trace = make_trace("bursty", n_requests, cap, 2, seed=11, max_new=2)
        return fe, replay(fe, trace)

    fe, row = one_run()
    got = set(row["completed"]) | set(row["rejected"])
    assert got == set(range(n_requests))
    assert not set(row["completed"]) & set(row["rejected"])
    stats = fe.stats()
    assert stats["totals"]["rejected"] == len(row["rejected"])
    assert len(row["completed"]) == (
        stats["totals"]["admitted"] - stats["totals"]["dup_completed"]
    )
    assert sum(r["submitted"] for r in stats["per_replica"]) == n_requests
    assert row["steps"] == sum(
        s["steps"] for s in stats["batchers"] if s
    )
    for rid, out in row["streams"].items():
        assert len(out) == 2, f"rid {rid} generated {len(out)} != max_new"

    _, row2 = one_run()
    assert row2["streams"] == row["streams"], "seeded replay must reproduce"
    assert row2["completed"] == row["completed"]
    assert row2["rejected"] == row["rejected"]


# ---------------------------------------------------------------------------
# the served construction gives the dense step's streams


@pytest.mark.parametrize("moe_ws", [
    pytest.param(False, id="llama3.2-3b"),
    pytest.param(True, id="kimi-k2-1t-a32b-moe-ws"),  # expert tiles too
])
def test_served_batcher_matches_dense_streams(moe_ws):
    """The benchmark's construction — one replica of the frontend over a
    ContinuousBatcher(jit_ws=True) — gives the same greedy token streams
    as the same load on the dense decode step (use_ws=False)."""
    if moe_ws:
        cfg = get_config("kimi-k2-1t-a32b", smoke=True).replace(
            moe_dispatch="ws")
        params = init_params(jax.random.PRNGKey(0), cfg)
    else:
        cfg, params = CFG, PARAMS
    prompts = [
        np.array([5, 6, 7, 8], np.int32),
        np.array([9, 8, 7], np.int32),
    ]
    streams = {}
    for label, kw in (("served", {"jit_ws": True}),
                      ("dense", {"use_ws": False})):
        fe = WorkStealingFrontend(
            lambda: ContinuousBatcher(params, cfg, slots=2, capacity=32, **kw),
            n_replicas=1,
        )
        for rid, p in enumerate(prompts):
            fe.submit(0, Request(rid, p, max_new=3))
        completed = fe.run(max_iters=50)
        assert set(completed) == {0, 1}
        streams[label] = {rid: r.out for rid, r in completed.items()}
    assert all(len(out) == 3 for out in streams["served"].values())
    assert streams["served"] == streams["dense"]
