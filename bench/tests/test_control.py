"""The lower-precision control comes out not correct, where the program
comes out correct, at a size a CPU test can hold: 12 layers of width 256
and a vocabulary of 8192, so that served tokens have small logit margins as
they do at the cells' sizes.  The control is the float32 reference computed
with float8 weights and bfloat16 activations (``bench/reference``); its
reading is the float32 reference's gap of the token the control puts first
at each served position."""
import copy
import time

import pytest

from bench import harness, spec
from bench.tests import tiny

WIDE = copy.deepcopy(tiny.TINY_CONFIG)
WIDE.update(name="tiny-wide", hidden_size=256, intermediate_size=512,
            num_hidden_layers=12, num_attention_heads=4,
            num_key_value_heads=2, head_dim=64, vocab_size=8192)
WIDE["repro"]["overrides"].update(n_layers=12, d_model=256, n_heads=4,
                                  n_kv_heads=2, d_head=64, d_ff=512,
                                  vocab_size=8192)
WIDE["engine"]["max_seq_len"] = 256
# readings at this size (CPU, seeds 1, 2**40+3, 7): program 0.030-0.052,
# control 0.35-0.57
LIMITS = dict(tiny.TINY_LIMITS, max_logit_gap=0.15, sample_tokens=256,
              sample_requests=16, min_tokens_checked=128)
MIX = dict(tiny.TINY_MIX, output={"dist": "uniform", "min": 8, "max": 32})


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    d = tmp_path_factory.mktemp("control")
    return d, spec.load_cell(tiny.make_tree(d, config=WIDE, mix=MIX,
                                            limits=LIMITS, cell="wide.mix"), d)


def test_control_fails_where_program_passes(cell):
    """The harness's own comparison, with the cell's limits, passes the
    program and fails the control on the same served sequences."""
    d, c = cell
    r = harness.run(c, 2 ** 40 + 3, 3.0, False, time.monotonic(),
                    require_tpu=False, root=d, control=True)
    assert r["correct"] is True, r["checks"]
    assert r["control_correct"] is False, r["readings"]
    prog, ctrl = r["readings"]["program"], r["readings"]["control"]
    assert prog["tokens"] == ctrl["tokens"] >= LIMITS["min_tokens_checked"]
