#!/usr/bin/env python3
"""What the program itself puts in a profiler trace, and the readings on it.

``trace_reduce`` keeps what the benchmark puts in a trace: its own
``bench.*`` host spans and each device operation's short name.  This module
keeps, beside that, what the program puts there (``repro.wstrace.spans``):

* the program's host spans (``engine.*``, ``frontend.*``, ``host.*``) with
  their stats, in ``ProgramTrace.spans``;
* the scope of each device operation (``ws_decode/dense``,
  ``ws_decode/kv_layout``, ``ws_decode/ws_put``, ``ws_decode/ws_kernel``;
  ``""`` for none), in ``ProgramTrace.scopes``, parallel to ``ops``.

The chip's operation events carry no ``op_name`` (on a v5e an event holds
the instruction's first HLO line and its device offsets), so an
operation's scope is the ``op_name`` metadata of the same instruction in
the decode program's compiled HLO text (``hlo_text``).  An instruction the
compiler added (a layout copy, an asynchronous copy's start and done) has
no ``op_name``; it takes the scope of the first instruction that uses its
result and has one, and is counted in ``inherited`` too.

Run as a script on a trace to print its breakdown::

    python3 bench/program_trace.py <file.xplane.pb> [--hlo decode.hlo.txt]
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from dataclasses import dataclass, field

import trace_reduce

SCOPES = ("ws_decode/dense", "ws_decode/kv_layout", "ws_decode/ws_put", "ws_decode/ws_kernel")
SPAN_PREFIXES = ("engine.", "frontend.", "host.")
_SCOPE = re.compile(r"ws_decode/(?:dense|kv_layout|ws_put|ws_kernel)\b")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")


@dataclass
class ProgramTrace(trace_reduce.Trace):
    # [(start, end, span name, {stat: value})], sorted by start
    spans: list = field(default_factory=list)
    # chip -> [scope of each op in ops[chip], "" for none]
    scopes: dict = field(default_factory=dict)
    # chip -> [True where that scope was inherited from a user]
    inherited: dict = field(default_factory=dict)

    def to_json(self) -> str:
        d = json.loads(super().to_json())
        d.update(spans=self.spans, scopes=self.scopes, inherited=self.inherited)
        return json.dumps(d)

    @classmethod
    def from_json(cls, text: str) -> "ProgramTrace":
        base = trace_reduce.Trace.from_json(text)
        d = json.loads(text)
        spans = [(s, e, n, dict(st)) for s, e, n, st in d.get("spans", [])]
        return cls(base.ops, base.modules, base.host, spans,
                   d.get("scopes", {}), d.get("inherited", {}))


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> (scope, inherited) for every instruction of a
    compiled HLO module that has a scope of its own or takes one from a
    user."""
    own, users = {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = re.search(r'op_name="([^"]*)"', rest)
        sc = _SCOPE.search(op.group(1)) if op else None
        own[name] = sc.group(0) if sc else ""
        args = rest.split("(", 1)[1] if "(" in rest else ""
        for operand in re.findall(r"%([\w.\-]+)", args.split("metadata=")[0]):
            users.setdefault(operand, []).append(name)
    out = {n: (s, False) for n, s in own.items() if s}

    def inherit(name, seen):
        if name in out:
            return out[name][0]
        if name in seen:
            return ""
        seen.add(name)
        for u in users.get(name, ()):
            s = inherit(u, seen)
            if s:
                return s
        return ""

    for name in own:
        if name not in out:
            s = inherit(name, set())
            if s:
                out[name] = (s, True)
    return out


def load(path: str, hlo_text: str | None = None) -> ProgramTrace:
    """``trace_reduce.load``'s reduction of an ``.xplane.pb``, with the
    program's spans and each device operation's scope."""
    import jax

    base = trace_reduce.load(path)
    tr = ProgramTrace(base.ops, base.modules, base.host)
    hlo = hlo_scopes(hlo_text or "")
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            chip = plane.name.rsplit(":", 1)[1]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    got = [hlo.get(e.name.partition(" = ")[0].lstrip("%"), ("", False))
                           for e in line.events]
                    tr.scopes[chip] = [s for s, _ in got]
                    tr.inherited[chip] = [i for _, i in got]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                             for e in line.events if e.name.startswith(SPAN_PREFIXES)]
    tr.spans.sort(key=lambda s: s[:2])
    return tr


# -- readings ---------------------------------------------------------------

def _decode_programs(tr, chip, t0, t1) -> list:
    return sorted((s, e) for s, e, n in tr.modules.get(chip, [])
                  if "decode" in n and t0 <= s < t1)


def decode_scope_ns(tr: ProgramTrace, t0, t1) -> dict:
    """Device time of the operations inside decode-step programs that start
    in ``[t0, t1)``, by scope (``""``: none; ``"inherited"``: the part
    whose scope came from a user), per program, averaged over chips;
    ``"program"`` is the busy union of those operations; empty where the
    window holds no decode program."""
    per = []
    for chip, ops in tr.ops.items():
        progs = _decode_programs(tr, chip, t0, t1)
        scopes = tr.scopes.get(chip)
        if not progs or scopes is None:
            continue
        tot: dict = {}
        busy = []
        inh = tr.inherited.get(chip) or [False] * len(ops)
        for (s, e, *_), sc, i in zip(ops, scopes, inh):
            if trace_reduce._inside(s, progs):
                tot[sc] = tot.get(sc, 0.0) + (e - s)
                if i:
                    tot["inherited"] = tot.get("inherited", 0.0) + (e - s)
                busy.append((s, e))
        tot["program"] = sum(b - a for a, b in trace_reduce.union(busy))
        per.append({k: v / len(progs) for k, v in tot.items()})
    if not per:
        return {}
    keys = set().union(*per)
    return {k: sum(p.get(k, 0.0) for p in per) / len(per) for k in keys}


def scope_ms(tr: ProgramTrace, t0, t1, scope: str):
    """Device ms per decode-step program under ``scope``; None where the
    trace has no scoped decode program."""
    ns = decode_scope_ns(tr, t0, t1)
    if not any(ns.get(s) for s in SCOPES):
        return None
    return ns.get(scope, 0.0) / 1e6


def _children(spans, parent, names) -> float:
    s0, e0 = parent[:2]
    return sum(e - s for s, e, n, _ in spans if n in names and s0 <= s and e <= e0)


def host_ms(tr: ProgramTrace, t0, t1):
    """Host time per engine step outside the wait for the device: each
    ``engine.step`` less its ``engine.step.sync``, plus each
    ``frontend.iteration`` less its ``engine.admit`` and ``engine.step``
    children, over the spans that start in ``[t0, t1)``, per step; None
    without steps."""
    inside = [s for s in tr.spans if t0 <= s[0] < t1]
    steps = [s for s in inside if s[2] == "engine.step"]
    if not steps:
        return None
    ns = sum(s[1] - s[0] - _children(inside, s, {"engine.step.sync"}) for s in steps)
    ns += sum(s[1] - s[0] - _children(inside, s, {"engine.admit", "engine.step"})
              for s in inside if s[2] == "frontend.iteration")
    return ns / 1e6 / len(steps)


def idle_gaps(tr: ProgramTrace, t0, t1, n: int = 10) -> list:
    """``trace_reduce.idle_gaps``, each gap named by the innermost span
    open in it, the program's spans counted with the benchmark's."""
    both = trace_reduce.Trace(tr.ops, tr.modules,
                              tr.host + [(s, e, nm) for s, e, nm, _ in tr.spans])
    return trace_reduce.idle_gaps(both, t0, t1, n)


def idle_by_span(tr: ProgramTrace, t0, t1) -> dict:
    """Every nanosecond in ``[t0, t1)`` with no operation on the first
    chip, charged to the innermost span open at that instant (program and
    benchmark spans alike, ``bench.window`` aside; ``host`` where none
    is): span name -> idle ms, largest first."""
    if not tr.ops:
        return {}
    ops = next(iter(tr.ops.values()))
    busy = trace_reduce.union(trace_reduce._clip([(s, e) for s, e, *_ in ops], t0, t1))
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [(s, e, n) for s, e, n in tr.host if n != "bench.window"]
    spans += [(s, e, n) for s, e, n, _ in tr.spans]
    # the innermost open span on each stretch between span boundaries
    cuts = sorted({t0, t1} | {x for s, e, _ in spans for x in (s, e) if t0 < x < t1})
    order = sorted(spans)
    pieces, active, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(order) and order[k][0] <= a:
            active.append(order[k])
            k += 1
        active = [sp for sp in active if sp[1] > a]
        name = min(active, key=lambda sp: sp[1] - sp[0])[2] if active else "host"
        pieces.append((a, b, name))
    out: dict = {}
    i = 0
    for a, b in gaps:
        while pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            pa, pb, name = pieces[j]
            out[name] = out.get(name, 0.0) + (min(b, pb) - max(a, pa)) / 1e6
            j += 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def breakdown(tr: ProgramTrace, t0, t1, n_ops: int = 8) -> dict:
    """The readings of one traced window, with each scope's longest
    operations (ms per decode program)."""
    ns = decode_scope_ns(tr, t0, t1)
    top: dict = {}
    for chip, ops in tr.ops.items():
        progs = _decode_programs(tr, chip, t0, t1)
        for (s, e, name, mk), sc in zip(ops, tr.scopes.get(chip, [])):
            if progs and trace_reduce._inside(s, progs):
                key = "ws_megakernel" if mk else name
                d = top.setdefault(sc or "none", {})
                d[key] = d.get(key, 0.0) + (e - s) / 1e6 / len(progs) / len(tr.ops)
    return {
        "decode_programs": len(_decode_programs(tr, next(iter(tr.ops), "0"), t0, t1)),
        "engine_steps": sum(1 for s in tr.spans if s[2] == "engine.step" and t0 <= s[0] < t1),
        "decode_ms": {k: v / 1e6 for k, v in sorted(ns.items())},
        "host_ms": host_ms(tr, t0, t1),
        "top_ops_ms": {sc: sorted(d.items(), key=lambda kv: -kv[1])[:n_ops]
                       for sc, d in sorted(top.items())},
        "idle_gaps": idle_gaps(tr, t0, t1),
        "idle_ms_by_span": idle_by_span(tr, t0, t1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--hlo", help="the decode program's compiled HLO text")
    args = ap.parse_args(argv)
    hlo = open(args.hlo).read() if args.hlo else None
    tr = load(args.xplane, hlo)
    try:
        w0, w1 = trace_reduce.window(tr)
    except ValueError:
        spans = [s[:2] for s in tr.spans] + [h[:2] for h in tr.host]
        w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)
    print(json.dumps(breakdown(tr, w0, w1), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
