"""A new architecture enters the benchmark as new files, and a layer kind
that no module declares is an error wherever it is looked up."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench import peaks, reference, spec, weights
from perfbench.metrics import mfu
from perfbench.tests import dropin, tiny
from perfbench.timeline import Record


def _files(root: Path) -> dict:
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts and ".out" not in p.parts}


def _in_copy(root: Path, *args: str, timeout: float = 600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{root}{os.pathsep}{root / 'src'}")
    return subprocess.run([sys.executable, "-m", "perfbench.tests.dropin",
                           *args], cwd=root, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.parametrize("program,correct", [("nope", True),
                                             ("rope", False)])
def test_new_family_runs_a_tiny_cell_end_to_end(tmp_path, program, correct):
    """The ``nope`` family module and a configuration that uses its kind,
    added to a copy, run a traced tiny cell there: weights, program,
    check and ``mfu``.  No file that the benchmark has is edited; served
    with the rotation that the kind leaves out, the cell is not
    correct."""
    root = tiny.make_copy(tmp_path, archs=(dropin.NOPE_MODEL,))
    new = dropin.add_family(root, "nope", dropin.NOPE)
    had = _files(tiny.ROOT / "perfbench")
    p = _in_copy(root, "cell", str(2**31 + 11), program)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is correct, res["checked"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    gap = res["checked"]["logit_gap.tiny-nope"]
    assert (gap["value"] <= gap["limit"]) is correct
    assert res["metrics"]["mfu"]["value"] > 0
    after = _files(root / "perfbench")
    assert {k: after[k] for k in had} == had
    assert new.relative_to(root / "perfbench") in set(after) - set(had)


def test_model_level_parts_apply_where_a_family_gives_them(tmp_path):
    """Embedding, residual and logit scales, extra leaves and a ``moe``
    part from one family module: the reference equals the plain walk on
    the weights rescaled by hand, bit for bit, and the counts agree."""
    root = tiny.make_copy(tmp_path)
    dropin.add_family(root, "probe", dropin.PROBE)
    p = _in_copy(root, "parts")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["logits_equal"] and out["logits_spread"] > 0
    assert out["meta"] == [4, 64]
    assert out["moe_leaves"] == ["wg", "wi", "wo"]
    assert out["counts_equal"]


def test_a_kind_declared_twice_is_refused_at_load(tmp_path):
    root = tiny.make_copy(tmp_path)
    dropin.add_family(root, "twice", "from perfbench.arch.olmo import KINDS\n")
    p = _in_copy(root, "parts", timeout=300)
    assert p.returncode != 0
    assert "'attn' is declared twice" in p.stderr
    assert "perfbench/arch/" in p.stderr


UNKNOWN = dict(tiny.TINY_MODELS["attn"], layer_pattern=["attn", "mamba9"])


@pytest.mark.parametrize("where", ["weights.layout", "reference.logits",
                                   "peaks.matmul_params",
                                   "peaks.token_flops",
                                   "peaks.paged_kv_bytes", "mfu"])
def test_unknown_kind_is_an_error_naming_it(where):
    import jax.numpy as jnp
    calls = {
        "weights.layout": lambda: weights.layout(
            spec.model_config({"model": UNKNOWN})),
        "reference.logits": lambda: reference.logits(
            {}, jnp.zeros((4,), jnp.int32), UNKNOWN),
        "peaks.matmul_params": lambda: peaks.matmul_params(UNKNOWN),
        "peaks.token_flops": lambda: peaks.token_flops(UNKNOWN, 64),
        "peaks.paged_kv_bytes": lambda: peaks.paged_kv_bytes(UNKNOWN, 64, 8),
        # never a share of the peak over a count of 0
        "mfu": lambda: mfu.read({
            "device_window": (0, 10**9), "win": {"span_s": 1.0},
            "model_dicts": [UNKNOWN],
            "recs": [Record(0, 0.0, node=0, prompt=[5] * 20,
                            tokens=[6] * 4)],
            "probe": type("P", (), {"prefix_prefills": []})(),
            "device": {"kind": "TPU v5 lite"}}),
    }
    with pytest.raises(ValueError, match=r"'mamba9'.*perfbench/arch/"):
        calls[where]()


@pytest.mark.parametrize("group,cls", [("ssm", "SSMConfig"),
                                       ("moe", "MoEConfig")])
def test_model_config_converts_nested_groups(group, cls):
    values = {"ssm": {"state_size": 8, "conv_width": 4, "expand": 2,
                      "num_heads": 2},
              "moe": {"num_experts": 8, "num_experts_per_tok": 2,
                      "expert_d_ff": 64}}[group]
    m = dict(tiny.TINY_MODELS["attn"], **{group: values})
    cfg = spec.model_config({"model": m})
    assert type(getattr(cfg, group)).__name__ == cls
    assert getattr(cfg, group) == type(getattr(cfg, group))(**values)
    assert cfg.layer_pattern == ("attn",)
