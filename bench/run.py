#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a model
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  One run:

1. set-up: random weights from the seed, made on the chip; one
   ``WorkStealingFrontend`` with one ``ContinuousBatcher(jit_ws=True)``
   replica per chip (the program's serving path, whose decode attention
   runs on the WS megakernel); every prefill bucket and the decode step
   warmed; the requests already under way when the window opens admitted;
2. the measured window: due requests submitted with ``fe.submit``, the
   engine driven by ``fe.run_iteration()``; each output token is timed when
   it reaches the host.  With ``--trace 1`` the profiler records the last
   seconds of the window and the per-layer metrics are read from it;
3. after the window: requests sent in it are served their first token,
   peak memory is read, the program's state is freed, and a sample of the
   finished requests is checked against a plain float32 reference;
4. the last line of standard output is one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
   also ``breakdown``), and last ``checks``, each compared number beside
   its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import registry  # noqa: E402
import stats  # noqa: E402
from loadgen import Traffic  # noqa: E402

TRACE_S = 4.0        # the profiler records the window's last seconds
DRAIN_S = 60.0       # after the window, at most this long to serve its requests
SAMPLE_TOKENS = 256  # served tokens checked against the reference, at least,
SAMPLE_REQUESTS = 12  # but no more requests than this, so that the reference
                      # (~1.5 s a request on one v5e) stays shorter than a window


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_device(chips: int) -> dict:
    """The device JAX found; exits 1 (no result) unless it is a TPU with at
    least ``chips`` chips."""
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"platform={d.platform} device_kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        log(f"FAIL: no TPU (JAX's default device is {d.platform!r})")
        sys.exit(1)
    if len(devs) < chips:
        log(f"FAIL: {chips} chips needed, {len(devs)} found")
        sys.exit(1)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def enable_cache() -> None:
    """The program's persistent compilation cache (``.jax_cache/`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` is set), every program in
    it, so that only a cell's first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCount:
    """Backend compilations, from JAX's monitoring events: the window must
    see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


def peak_bytes(n_chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n_chips])


class Harness:
    """Drives the program's frontend and keeps the host's record: when each
    request was due or sent, admitted, and served each token; each engine
    step's time and live lengths; each admission's time and prompt."""

    def __init__(self, conf, mix, seed, seconds, n_replicas, weights):
        import jax

        from repro.serving import ContinuousBatcher, WorkStealingFrontend

        self.jax = jax
        self.mix = mix
        self.traffic = Traffic(mix, seed, seconds, conf["vocab_size"])
        cfg = registry.model_config(conf)
        slots = int(conf["slots"])
        capacity = int(mix["capacity"])
        self.fe = WorkStealingFrontend(
            lambda: ContinuousBatcher(weights, cfg, slots=slots,
                                      capacity=capacity, jit_ws=True),
            n_replicas=n_replicas)
        self.logs: dict[int, stats.ReqLog] = {}
        self.asked: dict[int, int] = {}    # rid -> output tokens asked for
        self.client: dict[int, int] = {}   # rid -> closed-loop client
        self.steps: list = []              # (t0, t1, [kv lengths], latency_s)
        self.admits: list = []             # (t0, t1, prompt_len)
        self.lateness = 0.0
        self.phase = "setup"  # then "window", then "closed": clients stop sending
        self.now = time.perf_counter
        for b in self.fe.batchers:
            self._instrument(b)

    # -- spans around the program's layers ---------------------------------
    def _instrument(self, b):
        admit, step = b.admit, b.step
        ann = self.jax.profiler.TraceAnnotation

        def timed_admit(req):
            t = self.now()
            with ann("bench.admit"):
                ok = admit(req)
            t2 = self.now()
            if ok:
                lg = self.logs[req.rid]
                lg.admitted = t
                lg.token_times.append(t2)
                self.admits.append((t, t2, len(req.tokens)))
            return ok

        def timed_step():
            live = [(r, int(b.pos[i]) + 1) for i, r in enumerate(b.live) if r is not None]
            t = self.now()
            with ann("bench.step"):
                done = step()
            t2 = self.now()
            for r, _ in live:
                self.logs[r.rid].token_times.append(t2)
            if live:
                self.steps.append((t, t2, [n for _, n in live],
                                   b.metrics.step_latency_s[-1]))
            for r in done:
                self._completed(r.rid, t2)
            return done

        b.admit, b.step = timed_admit, timed_step

    def _completed(self, rid, t):
        self.logs[rid].completed = True
        c = self.client.pop(rid, None)
        if c is not None and self.phase != "closed":
            self.send(self.traffic.next_closed(), t, in_window=self.phase == "window",
                      client=c)

    def send(self, r, sent, *, in_window, client=None, warm=False):
        from repro.serving import Request

        self.logs[r.rid] = stats.ReqLog(r.rid, sent, in_window, warm=warm)
        self.asked[r.rid] = r.max_new
        if client is not None:
            self.client[r.rid] = client
        self.fe.submit(r.rid % len(self.fe.batchers), Request(r.rid, r.tokens, r.max_new))

    def iterate(self):
        with self.jax.profiler.TraceAnnotation("bench.iteration"):
            return self.fe.run_iteration()

    # -- phases --------------------------------------------------------------
    def warm_up(self):
        """Compile every program the cell's traffic uses: one prefill per
        prompt bucket, the cache splice, the decode step."""
        for r in self.traffic.warmup():
            self.send(r, self.now(), in_window=False, warm=True)
        while self.iterate():
            pass
        self.jax.block_until_ready(self.fe.batchers[0].caches)

    def start_steady(self):
        """Admit the requests already under way when the window opens: one
        per slot (open loop) or one per client (closed loop)."""
        if self.traffic.open:
            n = sum(b.B for b in self.fe.batchers)
            for r in self.traffic.steady_state(n):
                self.send(r, self.now(), in_window=False)
        else:
            for c, r in enumerate(self.traffic.steady_state(int(self.mix["clients"]))):
                self.send(r, self.now(), in_window=False, client=c)
        self.iterate()

    def window(self, seconds: float, trace_dir: str | None):
        t0 = self.now()
        t1 = t0 + seconds
        pending = sorted(self.traffic.pool, key=lambda r: r.due) if self.traffic.open else []
        k = 0
        tracing = None
        self.phase = "window"
        while True:
            now = self.now()
            if now >= t1:
                break
            if trace_dir and tracing is None and now >= t1 - TRACE_S:
                self.jax.profiler.start_trace(trace_dir)
                tracing = self.jax.profiler.TraceAnnotation("bench.window")
                tracing.__enter__()
                self.trace_t0 = self.now()
            while k < len(pending) and t0 + pending[k].due <= now:
                r = pending[k]
                self.lateness = max(self.lateness, now - (t0 + r.due))
                self.send(r, t0 + r.due, in_window=True)
                k += 1
            if not self.iterate():
                nxt = t0 + pending[k].due if k < len(pending) else t1
                with self.jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, min(nxt, t1) - self.now()))
        self.phase = "closed"
        if tracing is not None:
            tracing.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
        self.t0, self.t1 = t0, t1

    def drain(self):
        """Serve the first token of every request sent in the window, and
        run until at least one request has finished: at most DRAIN_S."""
        end = self.now() + DRAIN_S
        def owed():
            return (any(lg.in_window and not lg.token_times for lg in self.logs.values())
                    or not self.finished())
        while owed() and self.now() < end:
            if not self.iterate():
                break

    def finished(self) -> list:
        """Requests served to their end, outside the warm-up."""
        return [rid for rid in self.fe.completed if not self.logs[rid].warm]

    def account(self):
        """Mark in the record the requests rejected, finished more than
        once, or dropped: admitted, then neither still in a slot nor
        finished with as many tokens as they asked for."""
        for rid in self.fe.rejected:
            self.logs[rid].rejected = True
        live = {r.rid for b in self.fe.batchers for r in b.live if r is not None}
        for rid, lg in self.logs.items():
            done = self.fe.completed.get(rid)
            if done is not None:
                lg.dropped = len(done.out) != self.asked[rid]
            else:
                lg.dropped = lg.admitted is not None and rid not in live
        if self.fe.counters["dup_completed"]:
            # the frontend counts duplicates without naming them
            for lg in self.logs.values():
                lg.duplicated = lg.completed


def sample_requests(done: dict, seed: int) -> list:
    """Finished requests to check: the one with most served tokens, then
    others drawn from the seed until SAMPLE_TOKENS are covered or
    SAMPLE_REQUESTS are chosen."""
    rids = sorted(done, key=lambda rid: (-len(done[rid].out), rid))
    rng = np.random.default_rng(seed + 1)
    chosen = [rids[0]]
    rest = list(rng.permutation(rids[1:]))
    while (sum(len(done[r].out) for r in chosen) < SAMPLE_TOKENS and rest
           and len(chosen) < SAMPLE_REQUESTS):
        chosen.append(int(rest.pop()))
    return chosen


def check_outputs(weights, conf, done: dict, seed: int, pad_to: int,
                  control: bool = False) -> dict:
    """The widest gap between a served token's reference logit and the
    reference's best, over a sample of finished requests.  With
    ``control``, the float8 control's tokens stand in the served tokens'
    place at the same positions."""
    import reference

    if not done:
        return {"requests": 0, "tokens": 0, "gap_max": None}
    chosen = sample_requests(done, seed)
    g = np.concatenate([reference.served_gaps(weights, conf, done[rid].tokens,
                                              done[rid].out, pad_to, control=control)
                        for rid in chosen])
    return {"requests": len(chosen), "tokens": int(g.size), "gap_max": float(g.max())}


def judge(chk: dict, failed: int, limit: float) -> tuple[bool, dict]:
    """The verdict on one output check: at least one token checked, its
    widest gap within the limit, and no failed request; with each number
    compared beside its limit."""
    checks = {
        "logit_gap": {"value": chk["gap_max"], "limit": limit},
        "tokens_checked": {"value": chk["tokens"], "limit_min": 1},
        "failed_requests": {"value": failed, "limit": 0},
    }
    return chk["tokens"] >= 1 and chk["gap_max"] <= limit and failed == 0, checks


def trace_context(h: Harness, trace_dir: str, conf: dict, device: dict):
    """What per-layer readers read: the reduced trace with the steps and
    admissions of its traced part, and those of the whole window."""
    import peaks
    import trace_reduce

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0]
    tr = trace_reduce.load(path)
    w0, w1 = trace_reduce.window(tr)
    h0, h1 = h.trace_t0, h.t1
    return SimpleNamespace(
        conf=conf, peaks=peaks.peaks(device["kind"]), trace=tr, trace_window=(w0, w1),
        steps=[s for s in h.steps if h0 <= s[0] < h1],
        admits=[a for a in h.admits if h0 <= a[0] < h1],
        window_steps=[s for s in h.steps if h.t0 <= s[0] < h.t1],
        window_admits=[a for a in h.admits if h.t0 <= a[0] < h.t1])


def run_cell(spec: dict, wl: dict, conf: dict, mix: dict, seed: int, seconds: float,
             trace: bool, device: dict, control: bool = False, weights=None,
             tamper=None) -> dict:
    """One run of a cell: set-up, window, drain, check; returns the result.
    ``weights`` reuses a weight tree made from ``seed``; ``control`` also
    judges the float8 control's tokens at the same positions, under
    ``"control"``; ``tamper(harness)`` breaks the program underneath (the
    tests' planted faults)."""
    import jax

    import weights as weights_mod

    t_setup = time.perf_counter()
    if weights is None:
        weights = weights_mod.make_weights(conf, seed)
        jax.block_until_ready(weights)
    log(f"weights {time.perf_counter() - t_setup:.2f} s")
    compiles = CompileCount()
    h = Harness(conf, mix, seed, seconds, n_replicas=wl["chips"], weights=weights)
    if tamper is not None:
        tamper(h)
    h.warm_up()
    h.start_steady()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.2f} s; window {seconds} s")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        n0 = compiles.n
        h.window(seconds, trace_dir)
        in_window = compiles.n - n0
        h.drain()
        h.account()
        mem = peak_bytes(wl["chips"])
        ctx = trace_context(h, trace_dir, conf, device) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    served = [lg for lg in h.logs.values() if not lg.warm]
    attempted, failed = stats.attempted_failed(served)
    ttft = stats.ttft_ms(served)
    itl = stats.itl_gaps_ms(served, h.t0, h.t1)
    log(f"requests sent in the window {sum(lg.in_window for lg in served)}, "
        f"ttft samples {len(ttft)}, itl samples {len(itl)}, finished "
        f"{len(h.finished())}, generator late by at most {h.lateness * 1e3:.1f} ms, "
        f"compilations in the window {in_window}, frontend {h.fe.counters}")
    e2e = {
        "setup_s": setup_s,
        "output_tok_s": stats.output_tok_s(served, h.t0, h.t1),
        "itl_p50_ms": _pct(itl, 50),
        "itl_p95_ms": _pct(itl, 95),
        "itl_p99_ms": _pct(itl, 99),
        "ttft_p50_ms": _pct(ttft, 50),
        "ttft_p95_ms": _pct(ttft, 95),
    }
    log("all end-to-end readings " + json.dumps(e2e))
    done = {rid: h.fe.completed[rid] for rid in h.finished()}
    del h
    gc.collect()

    t_ref = time.perf_counter()
    chk = check_outputs(weights, conf, done, seed, int(mix["capacity"]))
    log(f"output check {time.perf_counter() - t_ref:.2f} s over {chk['requests']} requests")
    limit = float(conf["logit_gap_limit"])
    correct, checks = judge(chk, failed, limit)

    metrics = {}
    for m in registry.metrics_for(spec, wl["name"], trace):
        v = registry.load_metric_reader(m["name"])(ctx) if trace else e2e.get(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=mem)
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if control:
        cchk = check_outputs(weights, conf, done, seed, int(mix["capacity"]), control=True)
        ccorrect, cchecks = judge(cchk, failed, limit)
        out["control"] = {"correct": bool(ccorrect), "checks": cchecks}
    if trace:
        import trace_reduce

        w0, w1 = ctx.trace_window
        dev["busy_s"] = trace_reduce.busy_ns(ctx.trace, w0, w1) / 1e9
        dev["window_s"] = (w1 - w0) / 1e9
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(ctx.trace, w0, w1),
                            "idle_gaps": trace_reduce.idle_gaps(ctx.trace, w0, w1)}
    out["checks"] = checks
    return out


def _pct(values, q):
    return stats.percentile(values, q) if values else None


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        lim = ", ".join(f"{k} {v}" for k, v in c.items() if k != "value")
        log(f"check {name}: {c['value']} ({lim})")


def main(argv=None) -> int:
    args = parse_args(argv)
    spec = registry.load_benchmark()
    wl = registry.find_workload(spec, args.workload)
    conf = registry.load_config(wl["config"])
    mix = registry.load_traffic(wl["traffic"])
    enable_cache()
    device = check_device(wl["chips"])
    out = run_cell(spec, wl, conf, mix, args.seed, args.seconds, bool(args.trace), device)
    print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
