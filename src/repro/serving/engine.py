"""Continuous-batching serving engine + work-stealing request frontend.

Two layers:

* ContinuousBatcher — the device side: a fixed pool of B decode slots over
  stacked KV caches.  Admitting a request runs a batch-1 prefill and splices
  its caches into the slot (dynamic_update_slice on the batch dim); every
  engine step decodes all live slots in one decode step — by default
  `decode_step_ws`, which schedules the slots' ragged attention (and, with
  `cfg.moe_dispatch == "ws"`, the expert FFN) as tile tasks on the
  fence-free work-stealing megakernel; `use_ws=False` selects the jitted
  dense decode_step.  On a multi-device host, `cfg.moe_dispatch ==
  "mesh-ws"` shards the expert FFN's queues over the mesh "model" axis
  instead (repro.mesh_ws, DESIGN.md §7) — serving is the mesh dispatch's
  primary consumer, since it is forward-only.  Finished slots free
  immediately and are refilled the same step (the vLLM-style
  iteration-level scheduling, in JAX).

* WorkStealingFrontend — the host side: per-engine-replica request queues
  implemented with the *literal* WS-WMULT algorithm (paper Fig. 7).  Each
  replica's scheduler thread Takes from its own queue and Steals from busy
  replicas when idle; weak multiplicity means a request may be admitted by
  two replicas under contention — admission is idempotent (same tokens) and
  the frontend deduplicates on completion, keeping whichever finished first.
  This is the paper's fence-free load balancing as a serving feature: no
  lock and no CAS anywhere on the request hot path.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EMPTY, WSWMult
from repro.models import (
    decode_step,
    decode_step_ws,
    init_caches,
    prefill,
    ws_decode_supported,
)
from repro.wstrace import spans
from repro.wstrace.metrics import SchedulerMetrics


def jit_decode_step_ws(cfg, *, schedule: str = "ws", bk: int | None = None,
                       n_programs: int = 8):
    """Compiled end-to-end WS decode step: ``jit(decode_step_ws)`` with the
    config closed over (it carries static shape info) and ``(params,
    caches, tokens, pos)`` traced.

    Inside the trace the per-slot lengths are tracers, so every layer's
    attention queues — and, with ``cfg.moe_dispatch == "ws"``, the expert
    FFN queues — are built by the traced Put (fixed worst-case shapes, live
    masks) and drained by the same megakernel the eager path launches: the
    whole decode step, scheduler included, is one XLA computation.  One
    compilation per (slot count, capacity) shape, like the dense
    ``decode_step`` the batcher jits.
    """
    from repro.models import decode_step_ws as _ws

    def ws_decode_step(p, c, t, pos):
        return _ws(p, cfg, c, t, pos, schedule=schedule, bk=bk,
                   n_programs=n_programs)

    return jax.jit(ws_decode_step)


@dataclass
class Request:
    rid: int
    tokens: np.ndarray  # [T] int32 prompt
    max_new: int = 16
    out: List[int] = field(default_factory=list)


class ContinuousBatcher:
    def __init__(
        self,
        params,
        cfg,
        *,
        slots: int,
        capacity: int,
        greedy: bool = True,
        temperature: float = 1.0,
        sample_seed: int = 0,
        attn_schedule: str = "ws",
        use_ws: bool = True,
        jit_ws: bool = False,
    ):
        self.params, self.cfg = params, cfg
        self.B, self.cap = slots, capacity
        self.caches = init_caches(cfg, slots, capacity)
        self.live: List[Optional[Request]] = [None] * slots
        self.pos = np.zeros(slots, dtype=np.int32)  # next write slot per seq
        self.budget = np.zeros(slots, dtype=np.int32)
        self.greedy = greedy
        self.temperature = float(temperature)
        # seeded host-side sampler so greedy=False runs are reproducible
        self._rng = np.random.default_rng(sample_seed)
        # Decode attention schedule: with `use_ws` (the default) every
        # engine step routes the slots' ragged lengths through the
        # repro.pallas_ws scheduler ("ws" steals, "static" drains owner
        # queues).  `jit_ws` compiles that whole step — queues built by the
        # traced Put on device — instead of re-building queues host-side
        # each iteration.  `use_ws=False` selects the jitted dense
        # decode_step; an architecture decode_step_ws does not cover must
        # ask for it, so a WS run never turns dense behind the caller's back.
        if attn_schedule not in ("ws", "static"):
            raise ValueError(f"attn_schedule must be 'ws' or 'static': {attn_schedule!r}")
        self.attn_schedule = attn_schedule
        if use_ws and not ws_decode_supported(cfg):
            raise ValueError(
                f"use_ws=True: decode_step_ws does not cover {cfg.name!r} "
                "(it serves full-attention GQA decoders); pass use_ws=False "
                "for the dense decode step"
            )
        self.use_ws = bool(use_ws)
        if self.use_ws and jit_ws:
            self._decode = jit_decode_step_ws(cfg, schedule=attn_schedule)
        elif self.use_ws:
            self._decode = lambda p, c, t, pos: decode_step_ws(
                p, cfg, c, t, pos, schedule=attn_schedule
            )
        else:
            def dense_decode_step(p, c, t, pos):
                return decode_step(p, cfg, c, t, pos)

            self._decode = jax.jit(dense_decode_step)

        def prefill_step(p, b):
            return prefill(p, cfg, b, capacity=capacity)

        self._prefill = jax.jit(prefill_step)
        # per-step serving telemetry (latency percentiles, slot utilization,
        # admissions) — read it back via stats()
        self.metrics = SchedulerMetrics(slots=slots)
        spans.install_gc_spans()

    # -- sampling --------------------------------------------------------------
    def _select(self, logits) -> np.ndarray:
        """Next-token choice per row honoring the `greedy` flag: argmax, or
        seeded temperature sampling from softmax(logits / T)."""
        lg = np.asarray(logits, dtype=np.float32)
        if self.greedy:
            return lg.argmax(axis=-1)
        z = lg / max(self.temperature, 1e-6)
        z = z - z.max(axis=-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(axis=-1, keepdims=True)
        return np.array(
            [self._rng.choice(p.shape[-1], p=row) for row in p], dtype=np.int64
        )

    # -- admission ------------------------------------------------------------
    def _splice_slot(self, slot: int, c1) -> None:
        """Splice batch-1 prefill caches into the slot's batch row."""

        def splice(full, one):
            if not hasattr(one, "ndim"):
                return full
            return jax.lax.dynamic_update_slice_in_dim(full, one, slot, axis=1)

        self.caches = jax.tree_util.tree_map(splice, self.caches, c1)

    def admit(self, req: Request) -> bool:
        # a prompt of capacity-1 tokens is the longest the slot can hold:
        # the splice needs len(tokens) cache rows plus one for the first
        # generated token (admitting len >= capacity corrupts the splice)
        if not 0 < len(req.tokens) < self.cap:
            return False
        free = [i for i, r in enumerate(self.live) if r is None]
        if not free:
            return False
        slot = free[0]
        with jax.profiler.TraceAnnotation(spans.ENGINE_ADMIT, rid=int(req.rid),
                                          slot=slot, prompt_len=len(req.tokens)):
            with jax.profiler.TraceAnnotation(spans.ADMIT_PREFILL):
                batch = {"tokens": jnp.asarray(req.tokens, jnp.int32)[None, :]}
                logits, c1 = self._prefill(self.params, batch)
            with jax.profiler.TraceAnnotation(spans.ADMIT_SPLICE):
                self._splice_slot(slot, c1)
            with jax.profiler.TraceAnnotation(spans.ADMIT_FIRST_TOKEN):
                first = int(self._select(np.asarray(logits[:1]))[0])
        req.out.append(first)
        self.live[slot] = req
        self.pos[slot] = len(req.tokens)
        self.budget[slot] = req.max_new - 1
        self.metrics.record_admission()
        return True

    # -- one engine iteration ---------------------------------------------------
    def step(self) -> List[Request]:
        if not any(r is not None for r in self.live):
            return []
        with jax.profiler.TraceAnnotation(spans.ENGINE_STEP,
                                          step=len(self.metrics.step_latency_s),
                                          live=self.n_live):
            n_live = self.n_live
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(spans.STEP_INPUTS):
                tokens = np.zeros((self.B, 1), dtype=np.int32)
                for i, r in enumerate(self.live):
                    if r is not None:
                        tokens[i, 0] = r.out[-1]
                # per-slot decode positions (heterogeneous sequence lengths)
                tok, pos = jnp.asarray(tokens), jnp.asarray(self.pos)
            with jax.profiler.TraceAnnotation(spans.STEP_DISPATCH):
                logits, self.caches = self._decode(self.params, self.caches,
                                                   tok, pos)
            with jax.profiler.TraceAnnotation(spans.STEP_SYNC):
                lg = np.asarray(logits)
            with jax.profiler.TraceAnnotation(spans.STEP_SAMPLE):
                nxt = self._select(lg)
            self.metrics.record_step(time.perf_counter() - t0, n_live)
            done = []
            with jax.profiler.TraceAnnotation(spans.STEP_COMMIT):
                for i, r in enumerate(self.live):
                    if r is None:
                        continue
                    r.out.append(int(nxt[i]))
                    self.pos[i] += 1
                    self.budget[i] -= 1
                    if self.budget[i] <= 0 or self.pos[i] >= self.cap - 1:
                        done.append(r)
                        self.live[i] = None
                if done:
                    self.metrics.record_completion(len(done))
            return done

    def stats(self) -> dict:
        """Serving metrics snapshot: per-step latency p50/p99 (ms), mean
        slot utilization, admissions/completions (SchedulerMetrics)."""
        return self.metrics.snapshot()

    @property
    def n_live(self) -> int:
        return sum(r is not None for r in self.live)

    def live_lengths(self) -> np.ndarray:
        """Per-slot KV lengths (0 for free slots) — the ragged shape the
        ws attention path schedules over."""
        return np.where(
            np.array([r is not None for r in self.live]), self.pos, 0
        ).astype(np.int64)


def ragged_slot_attention(q, k_cache, v_cache, batcher_or_lengths, *, schedule=None,
                          bk=None):
    """Decode attention over a continuous batcher's ragged slots.

    The engine's decode slots always hold wildly different sequence lengths
    (that is the whole point of continuous batching), so a static attention
    grid wastes tile-slots on short slots while the longest slot serializes.
    This hands the live lengths to the fence-free work-stealing scheduler.

    ``q``: [B, H, hd] one query row per slot; ``k_cache``/``v_cache``:
    [B, Hkv, S, hd] stacked caches; ``batcher_or_lengths``: a
    :class:`ContinuousBatcher` or an explicit [B] length vector.  When
    ``schedule`` is None it follows the batcher's ``attn_schedule``
    ("ws" for a bare length vector).
    """
    from repro.pallas_ws.ragged import ragged_decode_attention

    if isinstance(batcher_or_lengths, ContinuousBatcher):
        lengths = batcher_or_lengths.live_lengths()
        schedule = batcher_or_lengths.attn_schedule if schedule is None else schedule
    else:
        lengths = np.asarray(batcher_or_lengths)
        schedule = "ws" if schedule is None else schedule
    return ragged_decode_attention(
        q, k_cache, v_cache, lengths, schedule=schedule, bk=bk
    )


class WorkStealingFrontend:
    """N engine replicas fed by WS-WMULT queues; idle replicas steal."""

    def __init__(self, make_batcher, n_replicas: int = 2, steal: bool = True,
                 max_admission_retries: int = 8, crash_plan=None):
        self.queues = [WSWMult(storage="linked", node_len=32) for _ in range(n_replicas)]
        self.batchers = [make_batcher() for _ in range(n_replicas)]
        self.steal = steal
        self.completed: Dict[int, Request] = {}
        # requests a batcher refused for cause (e.g. prompt >= cache
        # capacity) — surfaced here instead of being silently dropped
        self.rejected: Dict[int, Request] = {}
        # aggregate counters plus the per-replica scheduling history the
        # run used to discard — read both back via stats()
        self.counters = {
            "admitted": 0, "stolen": 0, "dup_completed": 0, "rejected": 0,
            "gave_up": 0, "readmitted": 0, "crashed": 0,
        }
        # Transient admissions (no free slot at admit time) back off
        # exponentially instead of hot-spinning the queue: retry n waits
        # 2^min(n,6) iterations, and after `max_admission_retries` the
        # request is surfaced in `rejected` (+ the "gave_up" counter)
        # rather than spinning run() to max_iters with zero progress.
        self.max_admission_retries = int(max_admission_retries)
        self._iter = 0
        self._backoff: List[List] = [[] for _ in range(n_replicas)]
        self._retries: Dict[int, int] = {}
        # Crash injection + idempotent re-admission (repro.chaos
        # ReplicaCrashPlan): `_orig[rid]` remembers each request's original
        # prompt/budget so a resumed copy (prompt ++ tokens-so-far,
        # remaining budget) can be reassembled into the full stream on
        # completion — no token is ever emitted twice, and greedy decode
        # makes the resumed stream identical to an uninterrupted one.
        self.crash_plan = crash_plan
        self.dead: set = set()
        self._orig: Dict[int, tuple] = {}
        self.per_replica = [
            {"submitted": 0, "admitted": 0, "stolen": 0, "completed": 0,
             "rejected": 0}
            for _ in range(n_replicas)
        ]
        # Per-replica rotating victim cursor: scanning victims from a fixed
        # origin (always replica 0 first) starves high-index replicas under
        # contention — every thief drains the low queues before ever looking
        # at the high ones.  Each successful or failed scan advances the
        # cursor so steal pressure spreads over all victims.
        self._victim_rr = [0] * n_replicas
        self._lock = threading.Lock()

    def submit(self, replica: int, req: Request):
        self._orig.setdefault(req.rid, (np.asarray(req.tokens), req.max_new))
        self.per_replica[replica]["submitted"] += 1
        self.queues[replica].put(req)

    def _reassemble(self, r: Request) -> Request:
        """Fold a resumed request's pre-crash emission back in: a resume
        copy carries prompt = original ++ already-emitted, so the full
        stream is that suffix plus this epoch's output."""
        orig = self._orig.get(r.rid)
        if orig is None:
            return r
        toks, max_new = orig
        if len(r.tokens) > len(toks):
            prev = [int(t) for t in np.asarray(r.tokens)[len(toks):]]
            return Request(r.rid, toks, max_new, prev + list(r.out))
        return r

    def _crash(self, rep: int) -> None:
        """Kill replica `rep`: its engine (slots, caches) is lost, its
        *queue* survives — queued-but-unadmitted requests stay stealable by
        the living replicas, which is the paper's whole point.  In-flight
        requests are re-admitted idempotently to survivors keyed by rid +
        tokens-generated-so-far."""
        b = self.batchers[rep]
        self.dead.add(rep)
        self.counters["crashed"] += 1
        survivors = [i for i in range(len(self.batchers))
                     if i not in self.dead]
        inflight, seen = [], set()
        for r in list(b.live):
            # one re-admission per request object, whatever slots hold it
            if r is not None and id(r) not in seen:
                seen.add(id(r))
                inflight.append(r)
        k = 0
        for r in inflight:
            rid = r.rid
            with self._lock:
                if rid in self.completed:
                    continue
            full = self._reassemble(r)
            emitted = list(full.out)
            toks, max_new = self._orig.get(
                rid, (np.asarray(r.tokens), r.max_new))
            remaining = max_new - len(emitted)
            if remaining <= 0:
                # the crash landed exactly on the completion boundary:
                # everything was already emitted — complete, don't resume
                with self._lock:
                    if rid in self.completed:
                        self.counters["dup_completed"] += 1
                    else:
                        self.completed[rid] = Request(
                            rid, toks, max_new, emitted)
                continue
            resume_tokens = np.concatenate([
                np.asarray(toks),
                np.asarray(emitted, dtype=np.asarray(toks).dtype),
            ]) if emitted else np.asarray(toks)
            resume = Request(rid, resume_tokens, remaining)
            tgt = survivors[k % len(survivors)] if survivors else rep
            k += 1
            self.counters["readmitted"] += 1
            self.per_replica[tgt]["submitted"] += 1
            self.queues[tgt].put(resume)

    def _next_request(self, replica: int) -> Optional[Request]:
        req = self.queues[replica].take()
        if req is not EMPTY:
            return req
        if self.steal and len(self.queues) > 1:
            victims = [v for v in range(len(self.queues)) if v != replica]
            start = self._victim_rr[replica] % len(victims)
            for j in range(len(victims)):
                v = victims[(start + j) % len(victims)]
                got = self.queues[v].steal(pid=1 + replica)
                if got is not EMPTY:
                    # resume past this victim next time
                    self._victim_rr[replica] = (start + j + 1) % len(victims)
                    self.counters["stolen"] += 1
                    self.per_replica[replica]["stolen"] += 1
                    return got
            self._victim_rr[replica] = (start + 1) % len(victims)
        return None

    def run_iteration(self) -> bool:
        """One round-robin pass over the replicas: fill free slots from the
        queues (honoring each admit's verdict), then step every busy
        batcher.  Returns True if anything happened — an admission, a
        rejection, or a live engine step."""
        with jax.profiler.TraceAnnotation(spans.FRONTEND_ITERATION):
            return self._iterate()

    def _iterate(self) -> bool:
        worked = False
        it = self._iter
        self._iter += 1
        if self.crash_plan is not None:
            for rep in self.crash_plan.due(it):
                if rep not in self.dead and rep < len(self.batchers):
                    self._crash(rep)
                    worked = True
        # release backed-off transients whose retry timer expired
        for rep, parked in enumerate(self._backoff):
            if parked:
                due = [e for e in parked if e[0] <= it]
                if due:
                    self._backoff[rep] = [e for e in parked if e[0] > it]
                    for _, req in due:
                        self.queues[rep].put(req)
        for rep, b in enumerate(self.batchers):
            if rep in self.dead:
                continue
            while b.n_live < b.B:
                req = self._next_request(rep)
                if req is None:
                    break
                # idempotent admission: a stolen duplicate re-runs prefill
                ok = b.admit(Request(req.rid, req.tokens, req.max_new))
                if not ok:
                    cap = getattr(b, "cap", None)
                    if cap is not None and not 0 < len(req.tokens) < cap:
                        # permanent: the prompt can never fit this engine's
                        # cache — surface it, don't retry
                        with self._lock:
                            if req.rid not in self.rejected:
                                self.rejected[req.rid] = req
                        self.counters["rejected"] += 1
                        self.per_replica[rep]["rejected"] += 1
                        worked = True
                        continue
                    # transient (no free slot despite the n_live check,
                    # e.g. a racing admission): bounded exponential backoff,
                    # then give up visibly — requeueing unconditionally
                    # could spin run() to max_iters with zero progress
                    n = self._retries.get(req.rid, 0) + 1
                    self._retries[req.rid] = n
                    if n > self.max_admission_retries:
                        with self._lock:
                            if req.rid not in self.rejected:
                                self.rejected[req.rid] = req
                        self.counters["rejected"] += 1
                        self.counters["gave_up"] += 1
                        self.per_replica[rep]["rejected"] += 1
                        worked = True
                        continue
                    self._backoff[rep].append((it + (1 << min(n, 6)), req))
                    break
                self.counters["admitted"] += 1
                self.per_replica[rep]["admitted"] += 1
                worked = True
            if b.n_live:
                for r in b.step():
                    self.per_replica[rep]["completed"] += 1
                    r = self._reassemble(r)
                    with self._lock:
                        if r.rid in self.completed:
                            self.counters["dup_completed"] += 1  # weak mult.
                        else:
                            self.completed[r.rid] = r
                worked = True
        # parked transients keep the loop alive until they retry or give up
        if any(self._backoff):
            worked = True
        return worked

    def run(self, max_iters: int = 10_000) -> Dict[int, Request]:
        """Drive all replicas round-robin until queues drain and slots empty."""
        for _ in range(max_iters):
            # an iteration with no admission and no live slot means every
            # queue answered EMPTY to take AND steal: fully drained.
            if not self.run_iteration():
                break
        return self.completed

    def stats(self) -> dict:
        """Scheduling history of the run: aggregate counters, per-replica
        submit/admit/steal/completion counts, and each batcher's
        SchedulerMetrics snapshot (when the batcher exposes one)."""
        out = {
            "totals": dict(self.counters),
            "per_replica": [dict(c) for c in self.per_replica],
        }
        snaps = []
        for b in self.batchers:
            snap = getattr(b, "stats", None)
            snaps.append(snap() if callable(snap) else None)
        out["batchers"] = snaps
        return out
