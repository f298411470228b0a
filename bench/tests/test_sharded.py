"""A cell across chips, on four host devices of the CPU: the weights drawn
split over them equal the one-device draw bit for bit, the reference on the
split weights gives the one-device gaps, and a tiny mixture of experts served
by the tensor- and expert-parallel ``ShardedEngine`` at tp=4 reads correct,
with the compile cache on as the benchmark keeps it for every cell.
Each case runs in a subprocess, since the device count is fixed when JAX
starts."""
import copy
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]

# tp=4 splits the KV heads four ways, so the tiny MoE takes 4 of them
MOE4 = copy.deepcopy(tiny.TINY_MOE)
MOE4.update(name="tiny-mixtral-kv4", num_key_value_heads=4, chips=4)
MOE4["repro"]["overrides"]["n_kv_heads"] = 4
MOE4["engine"]["tp"] = 4
MOE4_LIMITS = dict(tiny.TINY_LIMITS, router_tie_margin=0.021,
                   max_router_tie_share=0.5)

_SCRIPT = """
import json, sys, time
from pathlib import Path
sys.path[:0] = sys.argv[3:5]
import jax
import numpy as np
from bench import harness, spec, weights
from bench.reference.model import arch
case, d = sys.argv[1], Path(sys.argv[2])
configs = (json.loads((d / "configs.json").read_text())
           if case != "serve" else {})
devs = harness.chip_devices(4)
out = {}
if case == "draw":
    for name, hf in configs.items():
        a = arch(hf)
        for make in (weights.make, weights.make_program_params):
            one = jax.tree.leaves(make(a, 2 ** 40 + 7))
            four = jax.tree.leaves(make(a, 2 ** 40 + 7, devs))
            out[f"{name}.{make.__name__}"] = {
                "equal": all(np.array_equal(np.asarray(x), np.asarray(y))
                             for x, y in zip(one, four)),
                "split": sum(len(y.sharding.device_set) == 4
                             and not y.sharding.is_fully_replicated
                             for y in four),
                "leaves": len(four)}
elif case == "reference":
    rng = np.random.default_rng(3)
    for name, hf in configs.items():
        a = arch(hf)
        seqs = [(rng.integers(1, a.vocab, n).tolist(),
                 rng.integers(1, a.vocab, 9).tolist()) for n in (20, 300)]
        one = harness.reference_gaps(weights.make(a, 11), a, seqs)
        four = harness.reference_gaps(weights.make(a, 11, devs), a, seqs)
        # equal entries (a dense model's +inf margins too) differ by 0
        diff = lambda xs, ys: max(
            float(np.abs(np.where(x == y, 0, x - y)).max())
            for x, y in zip(xs, ys))
        out[name] = {"gap_diff": diff(one[0], four[0]),
                     "margin_diff": diff(one[1], four[1]),
                     "tokens": sum(len(g) for g in one[0])}
else:
    out["cache"] = harness.use_compile_cache(d)
    out["cache_enabled"] = bool(jax.config.jax_enable_compilation_cache)
    from repro.serving.sharded import ShardedEngine
    built = []
    orig = ShardedEngine.__init__
    def init(self, *a, **k):
        orig(self, *a, **k)
        built.append((self.tp, self.sharding_policy.ep,
                      sorted(self.trace_flags)))
    ShardedEngine.__init__ = init
    c = spec.load_cell(spec.list_cells(d)[-1], d)
    r = harness.run(c, 2 ** 36 + 5, 2.0, False, time.monotonic(),
                    require_tpu=False, root=d)
    out.update(correct=r["correct"], checks=r["checks"], engines=built)
print(json.dumps(out))
"""


def _run(case, d):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _SCRIPT, case, str(d),
                        str(ROOT / "src"), str(ROOT)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture
def configs(tmp_path):
    (tmp_path / "configs.json").write_text(json.dumps(
        {"dense": tiny.TINY_CONFIG, "moe": MOE4}))
    return tmp_path


def test_split_draw_equals_one_device_draw(configs):
    out = _run("draw", configs)
    assert set(out) == {f"{c}.{m}" for c in ("dense", "moe")
                        for m in ("make", "make_program_params")}
    for name, r in out.items():
        assert r["equal"], name
        # every matrix and bias is split; norm weights and the router whole
        assert 0 < r["split"] < r["leaves"], (name, r)


def test_reference_on_split_weights_gives_one_device_gaps(configs):
    out = _run("reference", configs)
    for name, r in out.items():
        assert r["tokens"] == 18
        # the split reference sums partial products in another order:
        # float32 rounding of logits of order one
        assert r["gap_diff"] <= 1e-5, (name, r)
        assert r["margin_diff"] <= 1e-5, (name, r)


def test_moe_served_across_four_chips_reads_correct(tmp_path):
    tiny.make_tree(tmp_path, config=MOE4, limits=MOE4_LIMITS,
                   cell="tiny-moe4.mix", chips=4)
    out = _run("serve", tmp_path)
    assert out["cache"] == str(tmp_path / ".jax_cache")
    assert out["cache_enabled"] is True
    assert any((tmp_path / ".jax_cache").iterdir())
    assert out["engines"] == [[4, True, ["ep_shard", "paged_shard"]]]
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == {"served_tokens_checked", "max_logit_gap",
                                  "router_tie_share"}
