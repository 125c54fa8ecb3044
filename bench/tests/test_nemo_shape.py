"""The output check at Mistral-NeMo's shape, at a size the CPU holds.

``mistral-nemo-12b-l10`` is the one configuration whose query width
(heads × head size, 32 × 128) is not its hidden size (5120), with an untied
head and 4 query heads per KV head.  This miniature keeps those ratios
(query width 4/5 of the hidden size, G = 4) and a closed mix over three
prompt buckets, and goes through the harness's own ``run_cell``: sound runs
of the program must pass the limit that the float8 control fails."""

import pytest

import run
from conftest import CPU

NEMO_MINI = {
    "name": "nemo-mini", "hidden_size": 160, "intermediate_size": 448,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 1000000.0, "sliding_window": None, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "logit_gap_limit": 0.03, "slots": 3,
}
# limit: sound runs of the program read at most 0.0052 over eight seeds, the
# float8 control at least 0.083 (CPU, seeds 1-6, 21, 2**35 + 22)

NEMO_MINI_CLOSED = {
    "loop": "closed", "clients": 3, "block": 6,
    "prompt": {"dist": "choice", "values": [8, 16, 24], "buckets": [8, 16, 24]},
    "output": {"dist": "uniform", "min": 3, "max": 8},
    "capacity": 32,
}


def test_miniature_keeps_nemo_ratios():
    c = NEMO_MINI
    assert c["num_attention_heads"] * c["head_dim"] * 5 == c["hidden_size"] * 4
    assert c["num_attention_heads"] // c["num_key_value_heads"] == 4
    assert not c["tie_word_embeddings"]


@pytest.mark.parametrize("seed", [2**35 + 22])
def test_control_fails_where_the_program_passes_at_nemo_shape(tiny_spec, seed):
    out = run.run_cell(tiny_spec, tiny_spec["workloads"][1], NEMO_MINI, NEMO_MINI_CLOSED,
                       seed, 1.0, False, CPU, control=True)
    assert out["correct"], out["checks"]
    ctl = out["control"]
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["logit_gap"]["value"] > ctl["checks"]["logit_gap"]["limit"]
    assert ctl["checks"]["tokens_checked"]["value"] == out["checks"]["tokens_checked"]["value"]
