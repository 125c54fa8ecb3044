"""Zero-cost experiments (paper §8.2, Figs 9a/9b): put-take and put-steal.

The owner performs N Puts followed by N Takes (or a thief performs N
Steals); no task work is attached.  We report wall µs/op AND the
instruction mix per operation (reads / writes / RMWs / lock acquisitions,
via the counting backend) — CPython's GIL hides hardware fence costs, so
the instruction mix is the architecture-independent evidence for the
paper's claim (WS-WMULT: zero RMW, zero locks, O(1) R/W per op; baselines:
CAS or locks on the Steal path).

The paper's result to reproduce: WS-WMULT fastest on put-take and
put-steal; B-WS-WMULT pays for its extra bookkeeping array; idempotent/
Chase-Lev/Cilk pay CAS or fence costs on Take/Steal.
"""

from __future__ import annotations

import time
from typing import Dict, List

from repro.core import ALGORITHMS, EMPTY

from .instrument import CountingBackend

DEFAULT_ALGOS = (
    "ws-wmult",
    "ws-wmult-array",
    "pallas-ws",
    "moe-ws",
    "b-ws-wmult",
    "ws-mult",
    "b-ws-mult",
    "chase-lev",
    "the-cilk",
    "idempotent-fifo",
    "idempotent-lifo",
    "idempotent-deque",
)

# The paper's headline structural claim, asserted (not just reported) by
# `audit_fence_free`: the WS-WMULT protocol and both device-layout shims —
# including the MoE expert-dispatch queue — touch shared memory with plain
# reads/writes only.  Zero RMW, zero lock acquisitions, on Put, Take AND
# Steal.  CPython can't count hardware fences, but every fence a TSO/ARM
# lowering would need hangs off an RMW or lock in these schemes, so this is
# the architecture-independent witness.
FENCE_FREE_ALGOS = ("ws-wmult", "ws-wmult-array", "pallas-ws", "moe-ws")


def _make(name: str, backend=None, n_ops: int = 0):
    """name 'x-array' selects the growable-array storage variant (the paper's
    WS_WMULT_ARRAY, §6 approach 1); plain ws-* use the linked-list (§6.2)."""
    base = name.replace("-array", "")
    if base in ("ws-mult", "ws-wmult", "b-ws-mult", "b-ws-wmult"):
        kw = dict(
            storage="growable" if name.endswith("-array") else "linked",
        )
        if kw["storage"] == "linked":
            kw["node_len"] = 4096
        else:
            kw["initial_len"] = 4096
    elif base in ("pallas-ws", "moe-ws"):
        # fixed-capacity device layout: size for the whole run
        kw = dict(capacity=n_ops + 8)
    else:
        kw = dict(initial_len=4096)
    return ALGORITHMS[base](backend=backend, **kw) if backend else ALGORITHMS[base](**kw)


def _payload_fn(name: str):
    """moe-ws is exercised with real encoded expert-tile records, so the
    audited Put/Take/Steal path is byte-for-byte the expert dispatch."""
    if name == "moe-ws":
        from repro.pallas_ws.tasks import ExpertTask

        return lambda i: tuple(
            int(v)
            for v in ExpertTask(
                expert=i % 64, row_start=8 * i, row_len=8, tid=i, cost=8
            ).encode()
        )
    return lambda i: i


def _run_ops(q, name: str, n_ops: int, steal: bool):
    payload = _payload_fn(name)
    for i in range(n_ops):
        q.put(payload(i))
    got = 0
    if steal:
        for _ in range(n_ops + 4):
            if q.steal(1) is not EMPTY:
                got += 1
            if got >= n_ops:
                break
    else:
        for _ in range(n_ops + 4):
            if q.take() is not EMPTY:
                got += 1
            if got >= n_ops:
                break
    return got


def bench_zero_cost(n_ops: int = 100_000, algos=DEFAULT_ALGOS, repeats: int = 3) -> List[Dict]:
    rows = []
    for steal in (False, True):
        exp = "put-steal" if steal else "put-take"
        for name in algos:
            best = float("inf")
            for _ in range(repeats):
                q = _make(name, n_ops=n_ops)
                t0 = time.perf_counter()
                got = _run_ops(q, name, n_ops, steal)
                dt = time.perf_counter() - t0
                best = min(best, dt)
            # instruction mix on a smaller run (counting overhead excluded
            # from the timed path)
            cb = CountingBackend()
            qc = _make(name, backend=cb, n_ops=2048)
            _run_ops(qc, name, 2048, steal)
            per_op = {k: round(v / 4096, 2) for k, v in cb.counts.snapshot().items()}
            rows.append(
                dict(
                    experiment=exp,
                    algorithm=name,
                    us_per_op=1e6 * best / (2 * n_ops),
                    extracted=got,
                    **{f"{k}_per_op": v for k, v in per_op.items()},
                )
            )
    return rows


def audit_fence_free(rows) -> None:
    """Assert the structural claim over measured instruction mixes: every
    FENCE_FREE_ALGOS row performed zero RMW operations and zero lock
    acquisitions, and every audited algorithm was measured on BOTH
    experiments — the Steal path is the one the claim is about, so it must
    not silently drop out of the bench."""
    seen = {}
    for r in rows:
        if r["algorithm"] not in FENCE_FREE_ALGOS:
            continue
        assert r["rmws_per_op"] == 0, (
            f"{r['algorithm']} [{r['experiment']}] performed RMWs: {r}"
        )
        assert r["locks_per_op"] == 0, (
            f"{r['algorithm']} [{r['experiment']}] took locks: {r}"
        )
        seen.setdefault(r["algorithm"], set()).add(r["experiment"])
    assert seen, "fence-free audit saw no rows"
    for algo, exps in seen.items():
        assert exps == {"put-take", "put-steal"}, (
            f"{algo} audited on {sorted(exps)} only — Take AND Steal required"
        )
    print(
        f"[zero-cost] fence-free audit OK: {sorted(seen)} at "
        "0 RMW / 0 locks per op on put-take and put-steal"
    )


_FORBIDDEN_HLO = (
    # any fence an implementation needs hangs off one of these; XLA spells
    # synchronization with these tokens when it emits it at all
    r"\batomic\w*", r"\bcmpxchg\b", r"\bcompare_and_swap\b", r"\brmw\w*",
    r"\bfence\w*", r"\bmutex\w*", r"\bsemaphore\w*", r"\bcritical\w*",
    r"\block\b", r"\bspinlock\w*",
)


def _fence_free_lowering_row(text: str, label: str, experiment: str,
                             algorithm: str, n_ops: int) -> Dict:
    """Scan one jit lowering's StableHLO text for the forbidden
    synchronization tokens (asserting none) and return its audit row — the
    single scan/row-schema implementation every audited lowering (forward
    AND backward) goes through."""
    import re

    hits = {
        pat: len(re.findall(pat, text, flags=re.IGNORECASE))
        for pat in _FORBIDDEN_HLO
        if re.search(pat, text, flags=re.IGNORECASE)
    }
    assert not hits, f"{label} contains synchronization ops: {hits}"
    return dict(
        experiment=experiment,
        algorithm=algorithm,
        n_ops=n_ops,
        hlo_bytes=len(text),
        reads_per_op="traced",  # plain tensor ops only; see hlo scan
        writes_per_op="traced",
        rmws_per_op=0,
        locks_per_op=0,
        fences_per_op=0,
    )


def audit_traced_put(n_tokens: int = 16, n_experts: int = 8, top_k: int = 2,
                     bt: int = 4, n_programs: int = 4) -> List[Dict]:
    """The traced-Put analogue of :func:`audit_fence_free`: lower the whole
    jit pipeline — queue construction (the device-side Put, padded
    `route_to_tasks_jax` + `make_queue_state_jax` AND the shared-pool
    `route_to_tasks_pool_jax` + `make_pool_queue_state_jax`) plus the
    megakernel drain (Take only, and Take+Steal under **both** victim
    selections: the sequential scan and the §3.6 cost-aware advisory
    argmax) — and assert the emitted StableHLO contains **zero** RMW /
    atomic / lock / fence operations.  The advisory `remaining` updates
    and the vectorized head/tail/argmax victim reads must lower to plain
    tensor ops like everything else.

    Since the dispatch grew its custom VJP (DESIGN.md §4.5) the audit also
    lowers ``jax.grad`` through ``expert_ffn_ws`` — the VJP's forward
    launch plus its backward under both ``grad_dispatch="dense"`` (plain
    gather/scatter transpose) and ``grad_dispatch="ws"`` (the second
    megakernel launch of per-row transpose tiles) — and holds the whole
    differentiated pipeline to the same zero-synchronization bar
    (``grad-dense`` / ``grad-ws`` rows).

    Since the dispatch went cross-device (DESIGN.md §7) the audit also
    lowers ``expert_ffn_mesh_ws`` — the ``shard_map``-ped two-phase mesh
    protocol: local drains, ring all-gather advisory exchange, replicated
    steal plan, psum delivery, pair combine — and holds it to the same bar
    (``put-steal-mesh`` row).  On a single-device session this audits the
    degenerate D=1 mesh; the CI ``mesh`` job re-audits on 8 forced host
    devices, where the collectives actually lower to collective-permute /
    all-reduce (plain data movement, never synchronization primitives).

    The host audit counts instructions through the backend cells; a traced
    Put has no backend cells, so the architecture-independent witness is the
    compiled program text itself: every shared-memory touch the lowering
    emits is a plain tensor read/write (scatters/gathers/dynamic-slices),
    never a synchronization primitive.  Returns one row per experiment in
    the bench_zero_cost row format, for BENCH_moe.json / BENCH.json.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.moe_ws.dispatch import (
        expert_queue_candidates,
        expert_rounds_bound,
        route_to_tasks_jax,
        route_to_tasks_pool_jax,
    )
    from repro.moe_ws.expert_kernel import run_moe_schedule
    from repro.pallas_ws.queues import (
        make_pool_queue_state_jax,
        make_queue_state_jax,
    )

    rng = np.random.RandomState(0)
    idx = np.stack([rng.choice(n_experts, top_k, replace=False)
                    for _ in range(n_tokens)]).astype(np.int32)
    gates = rng.uniform(0.2, 1.0, (n_tokens, top_k)).astype(np.float32)
    gates /= gates.sum(1, keepdims=True)
    d, f = 8, 16
    x = rng.randn(n_tokens, d).astype(np.float32)
    wg = rng.randn(n_experts, d, f).astype(np.float32)
    wu = rng.randn(n_experts, d, f).astype(np.float32)
    wd = rng.randn(n_experts, f, d).astype(np.float32)

    from repro.chaos import FaultPlan

    # (experiment label, steal, steal_policy, layout, trace, fault_plan) —
    # the traced-on cases audit the ISSUE-7 event rings: the per-extraction
    # record stores and the plain-write cursor bump must lower to the same
    # plain tensor ops as the queue protocol they instrument.  The faulted
    # case audits the ISSUE-9 chaos injection: stalls are initial clock
    # values and advisory corruption is plain data, so a fault-injected
    # lowering must meet the identical zero-synchronization bar.
    _faulted = FaultPlan(stalls=(2, 0, 1, 0), advisory="random")
    # the half-run cases (steal_run_cap=4) audit this PR's amortized Steal:
    # claiming a contiguous run with one probe + one coalesced advisory
    # write must lower to the same plain tensor ops as per-slot claims
    cases = (
        ("put-take", False, "cost", "padded", False, None, 1),
        ("put-steal", True, "scan", "padded", False, None, 1),
        ("put-steal", True, "cost", "padded", False, None, 1),
        ("put-steal", True, "cost", "pool", False, None, 1),
        ("put-steal-halfrun", True, "cost", "padded", False, None, 4),
        ("put-steal-halfrun", True, "cost", "pool", False, None, 4),
        ("put-take-traced", False, "cost", "padded", True, None, 1),
        ("put-steal-traced", True, "cost", "padded", True, None, 1),
        ("put-steal-halfrun-traced", True, "cost", "padded", True, None, 4),
        ("put-steal-faulted", True, "cost", "padded", True, _faulted, 1),
    )
    rows = []
    for exp, steal, policy, layout, trace, fault, cap in cases:
        n_queues = n_experts if steal else n_programs

        def pipeline(idx, gates, x, wg, wu, wd, steal=steal, policy=policy,
                     layout=layout, n_queues=n_queues, trace=trace,
                     fault=fault, cap=cap):
            rounds = expert_rounds_bound(
                n_tokens * top_k, bt, n_queues, n_programs, steal,
                steal_run_cap=cap,
            )
            if layout == "pool":
                rec, tail, off, routed = route_to_tasks_pool_jax(
                    idx, gates, n_experts, bt=bt
                )
                state = make_pool_queue_state_jax(
                    rec, tail, off, routed.loads, n_programs,
                    n_tasks=rec.shape[0],
                )
            else:
                records, live, routed = route_to_tasks_jax(
                    idx, gates, n_experts, bt=bt
                )
                cand, cand_live = expert_queue_candidates(records, live, n_queues)
                state = make_queue_state_jax(
                    cand, cand_live, n_programs,
                    n_tasks=records.shape[0] * records.shape[1],
                )
            res = run_moe_schedule(
                state, x, routed.tok_idx, wg, wu, wd, bt=bt, steal=steal,
                steal_policy=policy, rounds=rounds, trace=trace,
                fault_plan=fault, steal_run_cap=cap,
            )
            outs = (res.out, res.mult, res.head, res.taken, res.remaining)
            if trace:  # keep the rings live so their stores aren't DCE'd
                outs += (res.events, res.ev_cursor)
            return outs

        text = jax.jit(pipeline).lower(
            jnp.asarray(idx), jnp.asarray(gates), jnp.asarray(x),
            jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd),
        ).as_text()
        tag = (f"{policy},{layout}" + (",trace" if trace else "")
               + (",faulted" if fault is not None else "")
               + (f",cap{cap}" if cap > 1 else ""))
        rows.append(_fence_free_lowering_row(
            text, f"traced Put lowering [{tag}]", exp,
            f"moe-ws-traced[{tag}]", n_tokens * top_k,
        ))
    # backward lowering: jit(grad) through the custom VJP — forward
    # megakernel + no-drop-reference transpose, both backward evaluations
    from repro.moe_ws import expert_ffn_ws

    for gd in ("dense", "ws"):

        def grad_pipeline(gates, x, wg, wu, wd, gd=gd):
            loss = lambda gates, x, wg, wu, wd: (  # noqa: E731
                expert_ffn_ws(
                    idx, gates, x, wg, wu, wd, grad_dispatch=gd,
                    n_programs=n_programs, bt=bt,
                ) ** 2
            ).sum()
            return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
                gates, x, wg, wu, wd
            )

        text = jax.jit(grad_pipeline).lower(
            jnp.asarray(gates), jnp.asarray(x),
            jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd),
        ).as_text()
        rows.append(_fence_free_lowering_row(
            text, f"custom-VJP lowering [grad_dispatch={gd}]", f"grad-{gd}",
            f"moe-ws-vjp[{gd}]", n_tokens * top_k,
        ))
    # mesh lowering: the cross-device dispatch under shard_map — advisory
    # ring all-gathers, replicated steal plan, psum delivery, pair combine
    from repro.launch.mesh import make_expert_mesh
    from repro.mesh_ws import MESH_AXIS, expert_ffn_mesh_ws

    mesh = make_expert_mesh(n_experts)
    n_dev = mesh.shape[MESH_AXIS]

    def mesh_pipeline(idx, gates, x, wg, wu, wd):
        return expert_ffn_mesh_ws(
            idx, gates, x, wg, wu, wd, mesh=mesh, bt=bt,
            n_programs=n_programs,
        )

    text = jax.jit(mesh_pipeline).lower(
        jnp.asarray(idx), jnp.asarray(gates), jnp.asarray(x),
        jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd),
    ).as_text()
    rows.append(_fence_free_lowering_row(
        text, f"mesh dispatch lowering [D={n_dev}]", "put-steal-mesh",
        f"mesh-ws[D={n_dev}]", n_tokens * top_k,
    ))
    print(
        "[zero-cost] traced-put audit OK: moe-ws-traced jit lowering has "
        "0 RMW / 0 locks / 0 fences on put-take and put-steal "
        "(scan + cost policies, padded + pool layouts, half-run claims "
        "at steal_run_cap=4, event tracing off AND on, fault injection "
        "on), on the "
        "custom-VJP backward (grad-dense + grad-ws) and on the "
        f"shard_map mesh dispatch (D={n_dev})"
    )
    return rows


def audit_batched_put_host(n: int = 4096, segment: int = 64) -> List[Dict]:
    """Host-layout audit of the batched Put (amortized synchronization):
    count the shared-array instruction mix of :meth:`put_segment` versus
    the task-at-a-time :meth:`put` loop on the SAME payloads.  The segment
    path must issue strictly fewer queue-array writes per Put (one
    pre-clear pair and ONE advisory write per segment instead of per task),
    reach the identical final queue state, and clear the same fence-free
    bar: zero RMWs, zero lock acquisitions."""
    from benchmarks.instrument import CountingBackend
    from repro.pallas_ws import PallasWSHost

    cb_loop = CountingBackend()
    q_loop = PallasWSHost(backend=cb_loop, capacity=n + 2)
    for i in range(n):
        assert q_loop.put(i)
    cb_seg = CountingBackend()
    q_seg = PallasWSHost(backend=cb_seg, capacity=n + 2)
    for s in range(0, n, segment):
        assert q_seg.put_segment(range(s, min(s + segment, n)))
    assert q_loop.snapshot() == q_seg.snapshot(), "batched Put final-state"
    rows = []
    for exp, cb in (("put-loop", cb_loop), ("put-segment", cb_seg)):
        c = cb.counts.snapshot()
        rows.append(dict(
            experiment=exp,
            algorithm="pallas-ws-host-put",
            n_ops=n,
            reads_per_op=round(c["reads"] / n, 4),
            writes_per_op=round(c["writes"] / n, 4),
            rmws_per_op=c["rmws"],
            locks_per_op=c["locks"],
        ))
    loop_w = rows[0]["writes_per_op"]
    seg_w = rows[1]["writes_per_op"]
    assert seg_w < loop_w, (
        f"put_segment must amortize queue-array writes: {seg_w} vs {loop_w}"
    )
    assert all(r["rmws_per_op"] == 0 and r["locks_per_op"] == 0 for r in rows)
    print(
        f"[zero-cost] batched-put audit OK: {seg_w} vs {loop_w} queue-array "
        f"writes per Put (segment={segment}), 0 RMW / 0 locks on both"
    )
    return rows


# (experiment, config, overrides) of the served-step audit: a dense GQA
# step, and a MoE step whose expert FFN runs as WS expert tiles in the step
SERVED_STEP_CASES = (
    ("served-step-dense", "llama3.2-3b", {}),
    ("served-step-moe-ws", "kimi-k2-1t-a32b", {"moe_dispatch": "ws"}),
)


def audit_served_step(cases=SERVED_STEP_CASES) -> List[Dict]:
    """The whole-step analogue of :func:`audit_traced_put`: lower the
    compiled decode step the serving engine runs
    (``repro.serving.engine.jit_decode_step_ws`` — traced Put of every
    layer's decode tiles, and of the expert tiles under
    ``moe_dispatch="ws"``, drained by the WS megakernel) and assert the
    StableHLO carries **zero** RMW / atomic / lock / fence operations.

    Smoke configs at 2 slots × 32 positions; params, caches, tokens and
    positions are all traced, as in the engine.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import init_params, prefill
    from repro.serving.engine import jit_decode_step_ws

    cap = 32
    rows = []
    for exp, arch, overrides in cases:
        cfg = get_config(arch, smoke=True)
        if overrides:
            cfg = cfg.replace(**overrides)
        params = init_params(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jnp.asarray(
            np.array([[5, 6, 7, 8], [9, 8, 7, 6]], np.int32))}
        _, caches = prefill(params, cfg, batch, capacity=cap)
        tok = jnp.asarray(np.array([[3], [4]], np.int32))
        pos = jnp.asarray(np.array([4, 2], np.int32))
        text = jit_decode_step_ws(cfg).lower(params, caches, tok, pos).as_text()
        rows.append(_fence_free_lowering_row(
            text, f"served step lowering [{arch}]", exp,
            f"served-step[{arch}]", int(pos.size),
        ))
    print(
        "[zero-cost] served-step audit OK: the compiled WS decode step "
        f"({', '.join(c[1] for c in cases)}) has 0 RMW / 0 locks / 0 fences"
    )
    return rows


def main(n_ops: int = 100_000):
    rows = bench_zero_cost(n_ops)
    hdr = "experiment,algorithm,us_per_op,reads/op,writes/op,rmws/op,locks/op"
    print(hdr)
    out = [hdr]
    for r in rows:
        line = (
            f"{r['experiment']},{r['algorithm']},{r['us_per_op']:.3f},"
            f"{r['reads_per_op']},{r['writes_per_op']},{r['rmws_per_op']},{r['locks_per_op']}"
        )
        print(line)
        out.append(line)
    audit_fence_free(rows)
    rows.extend(audit_batched_put_host())
    try:
        import jax  # noqa: F401

        rows.extend(audit_traced_put())
        rows.extend(audit_served_step())
    except ImportError:
        print("[zero-cost] jax unavailable — traced-put audit skipped")
    return rows


if __name__ == "__main__":
    main()
