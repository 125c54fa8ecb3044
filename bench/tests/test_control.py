"""The output check's control at a size the CPU holds: the reference
computed in float8 (the step below the configuration's bfloat16) in the
program's place must fail the limit that sound runs of the program pass.
Both verdicts are the harness's own.  The chip readings at the cells'
own sizes come from ``bench/control.py``."""

import pytest

import reference
import run
import weights
from conftest import CPU, TINY_CLOSED, TINY_CONF


@pytest.mark.parametrize("seed", [11, 2**35 + 12, 13])
def test_control_fails_where_the_program_passes(tiny_spec, seed):
    out = run.run_cell(tiny_spec, tiny_spec["workloads"][1], TINY_CONF, TINY_CLOSED,
                       seed, 1.0, False, CPU, control=True)
    assert out["correct"], out["checks"]
    ctl = out["control"]
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["logit_gap"]["value"] > ctl["checks"]["logit_gap"]["limit"]
    assert ctl["checks"]["tokens_checked"]["value"] == out["checks"]["tokens_checked"]["value"]


def test_reference_ignores_padding_and_scores_every_served_token():
    w = weights.make_weights(TINY_CONF, 5)
    toks = [5, 9, 17, 3, 8, 100]
    a = reference.logits_at(w, TINY_CONF, toks, [2, 5], pad_to=8)
    b = reference.logits_at(w, TINY_CONF, toks, [2, 5], pad_to=32)
    assert a.shape == (2, TINY_CONF["vocab_size"])
    assert abs(a - b).max() < 1e-4
    best = a.argmax(-1)
    gaps = reference.served_gaps(w, TINY_CONF, toks[:3], [int(best[0]), 7, 1], pad_to=8)
    assert gaps.shape == (3,) and gaps[0] == 0.0 and (gaps >= 0).all()
