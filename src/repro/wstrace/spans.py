"""Names of the serving path's profiler spans and device scopes.

Both go through JAX's own profiler, so host spans and device operations
share one clock in the ``.xplane.pb`` that ``jax.profiler.trace`` writes:

* host spans are ``jax.profiler.TraceAnnotation``s opened by the engine
  (``engine.*``), the frontend (``frontend.*``) and a garbage-collector
  hook (``host.gc``).  Keyword arguments become the event's stats.  With
  no profiler session running a span records nothing;
* device scopes are ``jax.named_scope``s inside the jitted WS decode step.
  They exist only while the step is traced, and reach the compiled program
  as each operation's ``op_name`` metadata (``.../ws_decode/dense/...``).

Span tree of one engine iteration::

    frontend.iteration
      engine.admit {rid, slot, prompt_len}
        engine.admit.prefill      jitted batch-1 prefill dispatch
        engine.admit.splice       the slot's cache splice
        engine.admit.first_token  wait for the logits, then the choice
      engine.step {step, live}
        engine.step.inputs        token array and host-to-device copies
        engine.step.dispatch      call of the jitted decode step
        engine.step.sync          logits to host: the wait for the device
        engine.step.sample        next-token choice
        engine.step.commit        per-slot bookkeeping
    host.gc {generation}          any collection, wherever it lands

Every operation of the jitted WS decode step lies in one of
``DECODE_SCOPES``: ``dense`` (embedding, weight slices, norms,
projections, RoPE, MLP, head), ``kv_layout`` (per-layer cache slices, the
new token's write, the transposes for the kernel, the cache write-back),
``ws_put`` (lengths, task records, traced Put, the output's
normalisation) and ``ws_kernel`` (the megakernel launch).
"""

from __future__ import annotations

import gc

import jax

FRONTEND_ITERATION = "frontend.iteration"
ENGINE_ADMIT = "engine.admit"
ADMIT_PREFILL = "engine.admit.prefill"
ADMIT_SPLICE = "engine.admit.splice"
ADMIT_FIRST_TOKEN = "engine.admit.first_token"
ENGINE_STEP = "engine.step"
STEP_INPUTS = "engine.step.inputs"
STEP_DISPATCH = "engine.step.dispatch"
STEP_SYNC = "engine.step.sync"
STEP_SAMPLE = "engine.step.sample"
STEP_COMMIT = "engine.step.commit"
HOST_GC = "host.gc"

DENSE = "ws_decode/dense"
KV_LAYOUT = "ws_decode/kv_layout"
WS_PUT = "ws_decode/ws_put"
WS_KERNEL = "ws_decode/ws_kernel"
DECODE_SCOPES = (DENSE, KV_LAYOUT, WS_PUT, WS_KERNEL)

_gc_open: list = []  # the open host.gc span; collections never overlap


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        ann = jax.profiler.TraceAnnotation(HOST_GC, generation=info["generation"])
        ann.__enter__()
        _gc_open.append(ann)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def install_gc_spans() -> None:
    """Open a ``host.gc`` span around every garbage collection of this
    process; a second call does nothing."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
