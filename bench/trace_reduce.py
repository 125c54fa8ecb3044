"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
``Trace``: per chip the synchronous operations (the "XLA Ops" line of each
``/device:TPU:<n>`` plane) and the programs ("XLA Modules"), and the host
spans this benchmark opens (names starting with ``bench.``).  All times are
nanoseconds on the profiler's clock, which the host spans share.

The WS megakernel is the Pallas custom call (``custom_call_target=
"tpu_custom_call"``) run inside a decode-step program (a module whose name
has ``decode``): the program gives its ``pallas_call`` no name of its own.
"""

from __future__ import annotations

import bisect
import functools
import json
import re
from dataclasses import dataclass, field

@dataclass
class Trace:
    # chip -> [(start, end, short name, is_megakernel)]
    ops: dict = field(default_factory=dict)
    # chip -> [(start, end, module name)]
    modules: dict = field(default_factory=dict)
    # [(start, end, span name)]
    host: list = field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps({"ops": self.ops, "modules": self.modules, "host": self.host})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        tup = lambda rows: [tuple(r) for r in rows]
        return cls({k: tup(v) for k, v in d["ops"].items()},
                   {k: tup(v) for k, v in d["modules"].items()}, tup(d["host"]))


@functools.lru_cache(maxsize=None)
def short_name(hlo: str) -> str:
    """``%fusion.143 = bf16[128,14336]{...} fusion(...)`` -> ``fusion
    bf16[128,14336]``; a Pallas call -> ``tpu_custom_call``."""
    if 'custom_call_target="tpu_custom_call"' in hlo:
        return "tpu_custom_call"
    head, _, rest = hlo.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.lstrip("%"))
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{base} {shape.group(1)}" if shape else base


def load(path: str) -> Trace:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = plane.name.rsplit(":", 1)[1]
            for line in plane.lines:
                if line.name == "XLA Modules":
                    tr.modules[chip] = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                                        for e in line.events]
                elif line.name == "XLA Ops":
                    tr.ops[chip] = [(e.start_ns, e.start_ns + e.duration_ns,
                                     short_name(e.name), False) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events if e.name.startswith("bench.")]
    for chip, ops in tr.ops.items():
        decode = sorted((s, e) for s, e, n in tr.modules.get(chip, []) if "decode" in n)
        tr.ops[chip] = [(s, e, n, n == "tpu_custom_call" and _inside(s, decode))
                        for s, e, n, _ in ops]
    return tr


def _inside(t: float, intervals) -> bool:
    """``t`` lies in one of the sorted, disjoint ``intervals``."""
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and intervals[i][0] <= t < intervals[i][1]


def _clip(intervals, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in intervals if b > t0 and a < t1]


def union(intervals) -> list:
    """Merge overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def window(tr: Trace, span: str = "bench.window") -> tuple:
    """The traced window: the host span the benchmark opens around it."""
    spans = [(a, b) for a, b, n in tr.host if n == span]
    if not spans:
        raise ValueError(f"no {span!r} span in the trace")
    return spans[0]


def busy_ns(tr: Trace, t0, t1) -> float:
    """Device-busy time, the union of operation intervals, averaged over
    the chips."""
    per = [sum(b - a for a, b in union(_clip([(s, e) for s, e, *_ in ops], t0, t1)))
           for ops in tr.ops.values()]
    return sum(per) / len(per) if per else 0.0


def megakernel_ns(tr: Trace, t0, t1) -> float:
    """Device time of the WS megakernel's launches, averaged over chips."""
    per = [sum(b - a for a, b in _clip([(s, e) for s, e, _, mk in ops if mk], t0, t1))
           for ops in tr.ops.values()]
    return sum(per) / len(per) if per else 0.0


def top_ops(tr: Trace, t0, t1, n: int = 10) -> list:
    """The device operations that took most time, [name, seconds], summed
    over calls and averaged over chips."""
    tot: dict = {}
    for ops in tr.ops.values():
        for s, e, name, mk in ops:
            a, b = max(s, t0), min(e, t1)
            if b > a:
                key = "ws_megakernel" if mk else name
                tot[key] = tot.get(key, 0.0) + (b - a)
    k = max(1, len(tr.ops))
    return [[name, ns / k / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, t0, t1, n: int = 10) -> list:
    """The longest stretches with no operation on the first chip, each
    named by the innermost benchmark span open on the host in it
    (``host`` where none is), [name, seconds]."""
    if not tr.ops:
        return []
    ops = next(iter(tr.ops.values()))
    busy = union(_clip([(s, e) for s, e, *_ in ops], t0, t1))
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) / 2
        open_ = [(s, e, nm) for s, e, nm in tr.host
                 if s <= mid < e and nm != "bench.window"]
        name = min(open_, key=lambda x: x[1] - x[0])[2] if open_ else "host"
        out.append([name, (b - a) / 1e9])
    return out
