"""Sharding-rule unit tests (mesh-shape stubs; no 512-device init here)."""
from types import SimpleNamespace

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.distributed import sharding as sh


class StubMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = StubMesh({"data": 16, "model": 16})
POD = StubMesh({"pod": 2, "data": 16, "model": 16})


def _pol(mesh=MESH, mode="tp"):
    batch = ("pod", "data") if "pod" in mesh.shape else ("data",)
    return sh.ShardingPolicy(mesh, mode=mode, batch_axes=batch)


def test_sanitize_drops_nondivisible():
    spec = sh._sanitize(MESH, (50280, 2048), ("model", "data"))
    assert spec == P(None, "data")          # 50280 % 16 != 0
    spec2 = sh._sanitize(MESH, (32768, 2048), ("model", "data"))
    assert spec2 == P("model", "data")


def test_batch_entry_fallback_chain():
    pol = _pol(POD)
    assert sh._batch_entry(pol, 256) == ("pod", "data")   # 256 % 32 == 0
    assert sh._batch_entry(pol, 2) == "pod"               # only pod divides
    assert sh._batch_entry(pol, 3) is None
    fpol = sh.ShardingPolicy(POD, mode="fsdp", batch_axes=("pod", "data"))
    assert sh._batch_entry(fpol, 512) == ("pod", "data", "model")


def test_tp_mode_selection_per_arch():
    assert sh._tp_compatible(get_config("mixtral-8x22b"), 16)   # 48 heads
    assert sh._tp_compatible(get_config("qwen1.5-110b"), 16)    # 64 heads
    assert not sh._tp_compatible(get_config("qwen2-1.5b"), 16)  # 12 heads
    assert not sh._tp_compatible(get_config("minicpm3-4b"), 16)  # 40 heads
    assert sh._tp_compatible(get_config("mamba2-1.3b"), 16)     # 64 ssd heads
    assert sh._tp_compatible(get_config("zamba2-7b"), 16)       # 32 heads, 112 ssd


def test_param_rule_shapes():
    cfg = get_config("mixtral-8x7b")
    pol = _pol()
    # moe expert weights: (E, D, F) -> (None, fsdp, tp)
    rule = sh._param_rule(cfg, pol, ("layers", "ffn", "w_gate"), (8, 4096, 14336))
    assert rule == (None, "data", "model")
    rule = sh._param_rule(cfg, pol, ("layers", "attn", "wq"), (4096, 4096))
    assert rule == ("data", "model")
    rule = sh._param_rule(cfg, pol, ("embed",), (32000, 4096))
    assert rule == ("model", "data")


def test_activation_flags_seq_sharding():
    pol = _pol()
    f = sh.activation_shard_flags(pol, B=256, S=4096)
    assert f["batch"] == "data" and f["seq"] == "model"
    f2 = sh.activation_shard_flags(pol, B=1, S=1)      # decode, b=1
    assert f2["batch"] is None and f2["seq"] is None
    fpol = sh.ShardingPolicy(MESH, mode="fsdp", batch_axes=("data",))
    f3 = sh.activation_shard_flags(fpol, B=256, S=4096)
    assert f3["batch"] == ("data", "model") and f3["seq"] is None


# --------------------------------------------------------------------------- #
# decode-2D-TP / fallback records / paged pool specs / submesh allocator
# --------------------------------------------------------------------------- #
TP4 = StubMesh({"data": 2, "model": 4})


def _sds(*shape):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_decode_2d_tp_replicate_batch():
    pol = sh.ShardingPolicy(TP4, mode="tp", batch_axes=("data",),
                            replicate_batch=True)
    # hidden-state batch replicates (the data axis is freed for weight rows)
    assert sh._batch_entry(pol, 8) is None
    f = sh.activation_shard_flags(pol, B=8, S=1)
    assert f["batch"] is None and f["batch_size"] == 1
    # ...but the KV cache KEEPS batch sharding: attention stays shard-local
    # over batch slices while hidden states replicate
    cache = {"k": _sds(2, 8, 32, 2, 16)}
    spec = sh.cache_pspecs(get_config("qwen2-1.5b"), pol, cache)
    assert spec["k"] == P(None, "data", "model", None, None)


def test_paged_cache_pspecs_shards_heads_not_pages():
    import warnings
    cfg = get_config("qwen2-1.5b")
    pol = sh.ShardingPolicy(StubMesh({"data": 1, "model": 2}), mode="tp",
                            batch_axes=("data",))
    cache = {"kp": _sds(2, 16, 4, 64, 16), "ckvp": _sds(2, 16, 64, 32)}
    specs = sh.paged_cache_pspecs(cfg, pol, cache)
    # page axis must stay addressable from every shard → heads carry the
    # partition; MLA latent pool has no head axis and replicates
    assert specs["kp"] == P(None, None, "model", None, None)
    assert specs["ckvp"] == P(None, None, None, None)
    # KV head count not divisible by tp → honest fallback to replication
    pol4 = sh.ShardingPolicy(TP4, mode="tp", batch_axes=("data",))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", sh.ShardingFallback)
        bad = sh.paged_cache_pspecs(cfg, pol4, {"kp": _sds(2, 16, 2, 64, 16)})
    assert bad["kp"] == P(None, None, None, None, None)


def test_sharding_decision_records_fallbacks_and_warns_once():
    import warnings
    cfg = get_config("qwen2-1.5b")
    pol = sh.ShardingPolicy(TP4, mode="tp", batch_axes=("data",))
    params = {"unitA": {"attn": {
        "wq": _sds(64, 6),      # 6 % 4 → tp assignment dropped
        "wo": _sds(8, 64)}}}    # 8 % 4, 64 % 2 → kept
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        d = sh.sharding_decision(cfg, pol, params)
    assert [(f.path, f.axis_index, f.dim, f.axis) for f in d.fallbacks] == \
        [("unitA.attn.wq", 1, 6, "model")]
    assert 0.0 < d.tp_fallback_fraction < 1.0
    assert d.effective_tp == 4          # partial fallback keeps the degree
    assert len([x for x in w
                if issubclass(x.category, sh.ShardingFallback)]) == 1
    # identical decision re-records the fallback but does not re-warn
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        d2 = sh.sharding_decision(cfg, pol, params)
    assert not [x for x in w2 if issubclass(x.category, sh.ShardingFallback)]
    assert len(d2.fallbacks) == 1


def test_full_tp_fallback_reports_effective_tp_one():
    import warnings
    cfg = get_config("qwen2-1.5b")
    pol = sh.ShardingPolicy(TP4, mode="tp", batch_axes=("data",))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", sh.ShardingFallback)
        d = sh.sharding_decision(cfg, pol,
                                 {"unitB": {"attn": {"wq": _sds(6, 6)}}})
    assert d.tp_fallback_fraction == 1.0
    assert d.effective_tp == 1          # every tp dim replicated


def test_make_policy_tp_incompatible_and_ep_defaults():
    # 12 q-heads % 16 → automatic fsdp fallback instead of a broken TP plan
    pol = sh.make_policy(StubMesh({"data": 1, "model": 16}),
                         get_config("qwen2-1.5b"))
    assert pol.mode == "fsdp" and not pol.ep
    # mixtral: 8 experts % 2 == 0 → expert parallelism on by default
    pol2 = sh.make_policy(StubMesh({"data": 1, "model": 2}),
                          get_config("mixtral-8x7b"))
    assert pol2.mode == "tp" and pol2.ep


def test_submesh_allocator_alloc_release_oversubscribe():
    from repro.serving.sharded import SubmeshAllocator, SubmeshOversubscribed
    alloc = SubmeshAllocator()
    n = alloc.total_devices
    m = alloc.alloc((1, n))
    assert m.shape["model"] == n and m.shape["data"] == 1
    assert alloc.free_devices == 0
    assert alloc.try_alloc((1, 1)) is None
    with pytest.raises(SubmeshOversubscribed):
        alloc.alloc((1, 1))
    alloc.release(m)
    assert alloc.free_devices == n
    alloc.release(m)                     # idempotent
    assert alloc.free_devices == n


def test_dryrun_artifacts_exist_for_all_cells():
    """The committed dry-run artifacts must cover the full 40×2 matrix."""
    import json
    from pathlib import Path
    art = Path(__file__).resolve().parents[1] / "benchmarks" / "artifacts" / "dryrun"
    if not art.exists():
        pytest.skip("dry-run artifacts not generated yet")
    recs = [json.loads(p.read_text()) for p in art.glob("*.json")
            if "__" in p.name and p.name.count("__") == 2]
    cells = {(r["arch"], r["shape"], r["mesh"]) for r in recs}
    assert len(cells) >= 80
    bad = [r for r in recs if r.get("status") == "error"]
    assert not bad, bad[:2]
