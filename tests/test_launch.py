"""Launcher pieces the chip run depends on: the persistent compile
cache's directory, and the published-width node configs (checked from
shapes alone — no full-width weights are allocated on the CPU)."""
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.launch import cluster_serve, compile_cache
from repro.models import Model

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_leaves_a_set_env_dir_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []


def test_compile_cache_defaults_to_one_fixed_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert Path(first) == REPO / ".jax_cache"
    assert updates == [("jax_compilation_cache_dir", first)] * 2
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("arch", ["olmo-1b", "xlstm-350m"])
def test_published_node_config_is_the_registry_config(arch):
    cfg = cluster_serve.node_config(arch, smoke=False, vocab=200)
    assert cfg == get_config(arch)
    shapes = jax.eval_shape(
        lambda k: Model(cfg).init_params(k, max_seq=192),
        jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert {str(x.dtype) for x in leaves} == {"bfloat16"}
    n = sum(x.size for x in leaves)
    assert cluster_serve.param_bytes(cfg, 192) == 2 * n
    if arch == "olmo-1b":
        assert n == pytest.approx(1.18e9, rel=0.01)


def test_smoke_node_config_is_reduced():
    cfg = cluster_serve.node_config("olmo-1b", smoke=True, vocab=200,
                                    d_model=32)
    assert (cfg.d_model, cfg.num_layers, cfg.vocab_size, cfg.dtype) \
        == (32, 2, 200, "float32")


def test_published_cluster_refuses_weights_over_device_memory(monkeypatch):
    """qwen2-moe-a2.7b's ~28.6 GB of bf16 weights cannot share one 16 GB
    chip: the build stops before any weights exist."""
    monkeypatch.setattr(cluster_serve, "device_bytes_limit", lambda: 16e9)
    with pytest.raises(ValueError, match=r"qwen2-moe-a2\.7b 28\.63 GB"):
        cluster_serve.build_cluster(1, smoke=False,
                                    archs=("qwen2-moe-a2.7b",))
