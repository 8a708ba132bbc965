"""A tiny cell for CPU rehearsals: a copy of the benchmark in a
temporary directory, with one more configuration, traffic mix and cell
added from data files alone."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_MODELS = {
    "attn": {"name": "tiny-attn", "arch_type": "dense", "num_layers": 2,
             "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "d_ff": 128,
             "vocab_size": 4096, "head_dim": 16, "mlp_type": "swiglu",
             "norm_type": "nonparametric", "rope_theta": 10000.0,
             "pos_embedding": "rope", "layer_pattern": ["attn"],
             "tie_embeddings": True, "dtype": "float32"},
    "xlstm": {"name": "tiny-xlstm", "arch_type": "ssm", "num_layers": 2,
              "d_model": 64, "num_heads": 2, "num_kv_heads": 2, "d_ff": 0,
              "vocab_size": 4096, "head_dim": 32, "mlp_type": "none",
              "norm_type": "layernorm", "pos_embedding": "none",
              "layer_pattern": ["mlstm", "slstm"],
              "ssm": {"state_size": 8, "conv_width": 4, "expand": 2,
                      "num_heads": 2},
              "tie_embeddings": True, "dtype": "float32"},
    "hymba": {"name": "tiny-hymba", "arch_type": "hybrid", "num_layers": 2,
              "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "d_ff": 96,
              "vocab_size": 4096, "head_dim": 16, "mlp_type": "swiglu",
              "norm_type": "rmsnorm", "rope_theta": 10000.0,
              "sliding_window": 48, "layer_pattern": ["hymba"],
              "ssm": {"state_size": 8, "conv_width": 4, "expand": 2,
                      "num_heads": 2},
              "tie_embeddings": False, "dtype": "float32"},
}


def tiny_traffic() -> dict:
    spec = json.loads((ROOT / "perfbench" / "traffic" / "rag-steady.json")
                      .read_text())
    spec["corpus"] = {"entities": 60, "facts_per_doc": 3, "chunk_words": 24,
                      "doc_words": {"median": 50, "sigma": 0.6, "min": 30,
                                    "max": 150}}
    spec["top_k"] = 2
    spec["answer_tokens"] = 16
    spec["prompt_overhead"] = 40
    spec["limits"] = {"latency_s": 60.0, "ttft_s": 60.0}
    spec["ppo_update_threshold"] = 6       # crossed inside the window
    spec["warmup"] = {"pool": 12, "max_slot": 4, "slots": [2, 2]}
    return spec


def make_copy(tmp: Path, archs=("attn", "xlstm"), knee: float = 1.5,
              limits=None) -> Path:
    """A copy of BENCHMARK.json and perfbench/ under ``tmp`` with the cell
    ``tiny.rag`` added by data files only.  ``archs`` names models of
    ``TINY_MODELS`` or gives a model's dict."""
    root = tmp / "checkout"
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    models = [a if isinstance(a, dict) else TINY_MODELS[a] for a in archs]
    name = "tiny-" + "-".join(a if isinstance(a, str) else a["name"]
                              for a in archs)
    nodes = [{"arch": m["name"], "source": "test",
              "note": "CPU rehearsal size", "model": m} for m in models]
    cfg = {"name": name, "deployment": "test", "knee_rps": knee,
           "reduced": [], "dtype": "float32", "nodes": nodes,
           "check": {"limits": limits or {
               "retrieval_gap": 1e-6,
               **{f"logit_gap.{n['arch']}": 1e-3 for n in nodes}}}}
    (root / "perfbench" / "configs" / f"{name}.json").write_text(
        json.dumps(cfg))
    (root / "perfbench" / "traffic" / "tiny-rag.json").write_text(
        json.dumps(tiny_traffic()))
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"perfbench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.rag", "config": name,
                               "traffic": "tiny-rag", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.rag")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "src").symlink_to(ROOT / "src")
    return root
