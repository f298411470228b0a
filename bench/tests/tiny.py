"""A tiny benchmark tree for CPU tests: the repository's ``bench/`` files
plus one more configuration, traffic mix, limits file and cell, added as
files only."""
from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "tiny-qwen2",
    "source": "test configuration at tiny widths",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "tie_word_embeddings": True, "qkv_bias": True,
    "reduced": [], "assumed": {},
    "repro": {"arch": "qwen2-1.5b",
              "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                            "n_kv_heads": 2, "d_head": 16, "d_ff": 128,
                            "vocab_size": 512}},
    "engine": {"slots": 4, "max_seq_len": 128, "page_size": 16,
               "hardware": "TPU-v5e", "tp": 1, "replicas": 1},
    "chips": 1,
}

TINY_MOE = dict(copy.deepcopy(TINY_CONFIG), name="tiny-mixtral",
                num_local_experts=4, num_experts_per_tok=2,
                tie_word_embeddings=False, qkv_bias=False, rms_norm_eps=1e-05)
TINY_MOE["repro"] = {"arch": "mixtral-8x7b",
                     "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                                   "n_kv_heads": 2, "d_head": 16, "d_ff": 128,
                                   "vocab_size": 512, "n_experts": 4,
                                   "norm_eps": 1e-05}}

TINY_MIX = {
    "why": "test", "loop": "open",
    "arrival": {"process": "poisson", "rate_per_s": 6.0}, "preroll_s": 0.5,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 60},
    "output": {"dist": "uniform", "min": 4, "max": 12},
    "prefix": {"count": 2, "tokens": 32, "zipf_s": 1.0},
}

TINY_LIMITS = {"max_logit_gap": 0.05, "min_tokens_checked": 16,
               "sample_tokens": 64, "sample_requests": 8}


def make_tree(dst: Path, config=TINY_CONFIG, mix=TINY_MIX,
              limits=TINY_LIMITS, cell="tiny.mix", chips=1) -> str:
    """Copy the repository's benchmark into ``dst`` and add one cell by
    adding files and a ``workloads`` entry; returns the cell's name."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg_name, mix_name = config["name"], cell.split(".", 1)[1]
    (dst / "bench" / "configs" / f"{cfg_name}.json").write_text(json.dumps(config))
    (dst / "bench" / "traffic" / f"{mix_name}.json").write_text(json.dumps(mix))
    (dst / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(limits))
    bench["configs"].append({"name": cfg_name, "source": "test",
                             "file": f"bench/configs/{cfg_name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": cfg_name,
                               "traffic": mix_name, "chips": chips,
                               "why": "test"})
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return cell
