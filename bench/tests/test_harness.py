"""The harness end to end on the CPU at tiny widths: a cell added by adding
files only, what each run prints, and refusals."""
import copy
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness, spec
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 35 + 99


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    d = tmp_path_factory.mktemp("tree")
    return d, tiny.make_tree(d)


def test_new_cell_is_listed_from_its_files(tree):
    d, cell = tree
    assert cell in spec.list_cells(d)
    assert set(spec.list_cells(ROOT)) < set(spec.list_cells(d))
    c = spec.load_cell(cell, d)
    assert c.config["name"] == "tiny-qwen2" and c.traffic["loop"] == "open"
    assert {m.name for m in c.per_layer} == {
        m["name"] for m in json.loads((d / "BENCHMARK.json").read_text())
        ["per_layer"]}
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such.cell", d)


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "per_layer"])
def test_run_on_cpu(tree, trace):
    d, cell = tree
    c = spec.load_cell(cell, d)
    r = harness.run(c, SEED, 2.0, trace, time.monotonic(),
                    require_tpu=False, root=d)
    assert r["correct"] is True
    assert r["attempted"] == round(c.traffic["arrival"]["rate_per_s"] * 2.0)
    assert r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["max_logit_gap"]["value"] <= \
        r["checks"]["max_logit_gap"]["limit"]
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    names = set(r["metrics"])
    if trace:
        # the trace readers find nothing on a CPU (no device plane), the
        # host-span readers do
        assert {"queue_wait_p90_s", "prefill_ms_per_ktok", "decode_step_ms",
                "prefix_hit_share"} <= names
        assert all(r["metrics"][m]["value"] >= 0 for m in names)
    else:
        assert names == {m.name for m in c.end_to_end}
        assert all(v["value"] > 0 for v in r["metrics"].values())


def _run_py(cwd, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_without_a_tpu():
    p = _run_py(ROOT, "--workload", "qwen2-1.5b.chat", "--seed", str(SEED),
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_py_refuses_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    p = _run_py(tmp_path, "--workload", "qwen2-1.5b.chat", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


def _two_replicas():
    cfg = copy.deepcopy(tiny.TINY_CONFIG)
    cfg["engine"]["replicas"] = 2
    return cfg


def test_plan_comes_from_the_config_file(tmp_path):
    cell = tiny.make_tree(tmp_path, config=_two_replicas())
    c = spec.load_cell(cell, tmp_path)
    with pytest.raises(spec.SpecError, match="2 chips"):
        harness.replica_group(c, "tiny-qwen2")


_STEP_EVERY_ENGINE = """
import json, sys, time
from pathlib import Path
sys.path[:0] = sys.argv[2:4]
from bench import harness, spec
from repro.serving import engine as E
calls = {}
orig = E.Engine.step
def step(self):
    calls[id(self)] = calls.get(id(self), 0) + 1
    return orig(self)
E.Engine.step = step
d = Path(sys.argv[1])
c = spec.load_cell(spec.list_cells(d)[-1], d)
r = harness.run(c, 2 ** 36 + 1, 2.0, False, time.monotonic(),
                require_tpu=False, root=d)
print(json.dumps({"correct": r["correct"], "steps": sorted(calls.values())}))
"""


def test_every_replica_is_warmed_and_stepped(tmp_path):
    """Two replicas on two (host) devices: both engines are built from the
    file's plan and both serve."""
    tiny.make_tree(tmp_path, config=_two_replicas(), chips=2)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", _STEP_EVERY_ENGINE,
                        str(tmp_path), str(ROOT / "src"), str(ROOT)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert len(out["steps"]) == 2
    # more than each engine's warm-up: traffic reached both
    assert min(out["steps"]) > 2 * harness.WARMUP_NEW



_CACHE_RULE = """
import json, sys
from pathlib import Path
sys.path[:0] = sys.argv[2:4]
import jax, jax.numpy as jnp
from bench import harness
d = Path(sys.argv[1])
where = harness.use_compile_cache(d)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(json.dumps({"where": where,
                  "files": len(list((d / ".jax_cache").glob("*")))}))
"""


def test_compile_cache_is_kept_in_the_checkout(tmp_path):
    """The cache is at ``<root>/.jax_cache``, and a compile of any length
    lands there."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", _CACHE_RULE, str(tmp_path),
                        str(ROOT / "src"), str(ROOT)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["where"] == str(tmp_path / ".jax_cache") and out["files"] > 0
