"""A whole run at a size the CPU holds (the program's kernels interpreted),
sound and with the timed path broken underneath: ``correct`` must come out
true for the sound run and false for every planted fault a serving cell
can have.  (One chip: no exchange between chips to leave out.)"""

import numpy as np
import pytest

import run
from conftest import CPU, TINY_CLOSED, TINY_CONF


def _state_unchanged(h):
    """The decode step hands back the caches it was given."""
    for b in h.fe.batchers:
        dec = b._decode
        b._decode = lambda p, c, t, pos, dec=dec: (dec(p, c, t, pos)[0], c)


def _half_batch(h):
    """The decode step computes only the first half of the slots."""
    for b in h.fe.batchers:
        dec = b._decode

        def half(p, c, t, pos, dec=dec, B=b.B):
            logits, c2 = dec(p, c, t, pos)
            return logits.at[B // 2:].set(0.0), c2

        b._decode = half


def _token_altered(h):
    """Slot 0's next token is changed where the engine picks it."""
    for b in h.fe.batchers:
        sel = b._select

        def altered(logits, sel=sel, V=TINY_CONF["vocab_size"]):
            out = np.array(sel(logits))
            if out.shape[0] > 1:
                out[0] = (out[0] + 1) % V
            return out

        b._select = altered


def _slot_freed(h):
    """The engine frees a slot mid-request, once: the request is dropped
    unfinished and never completes."""
    for b in h.fe.batchers:
        step = b.step

        def freeing(step=step, b=b, freed=[]):
            done = step()
            for i, r in enumerate(b.live):
                if r is not None and b.budget[i] > 1 and not freed:
                    b.live[i] = None
                    freed.append(r.rid)
            return done

        b.step = freeing


def _run(spec, tamper=None, seed=3):
    wl = spec["workloads"][1]
    return run.run_cell(spec, wl, TINY_CONF, TINY_CLOSED, seed, 2.0, False, CPU,
                        tamper=tamper)


def test_sound_run_is_correct(tiny_spec):
    out = _run(tiny_spec)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s", "itl_p95_ms"}


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _token_altered],
                         ids=["state_unchanged", "half_batch", "token_altered"])
def test_planted_fault_is_not_correct(tiny_spec, fault):
    out = _run(tiny_spec, fault)
    assert not out["correct"], out["checks"]
    c = out["checks"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_request_dropped_mid_way_is_failed(tiny_spec):
    out = _run(tiny_spec, _slot_freed)
    assert not out["correct"], out["checks"]
    assert out["failed"] == 1 and out["checks"]["failed_requests"]["value"] == 1
