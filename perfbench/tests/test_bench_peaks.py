"""Peaks table and work counted from shapes."""
import json
from pathlib import Path

import pytest

from perfbench import peaks

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _model(cfg, arch):
    c = json.loads((CONFIGS / f"{cfg}.json").read_text())
    return next(n["model"] for n in c["nodes"] if n["arch"] == arch)


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v99 imaginary")
    f, b = peaks.peaks("TPU v5 lite")
    assert f == 197e12 and b == 819e9


def test_olmo_weights_and_flops_from_shapes():
    m = _model("edge2-olmo1b-xlstm350m", "olmo-1b")
    # 16 layers x (4 x 2048^2 attention + 3 x 2048 x 8192 MLP)
    assert peaks.matmul_params(m) == 16 * (4 * 2048 ** 2 + 3 * 2048 * 8192)
    f1 = peaks.token_flops(m, 1)
    f2 = peaks.token_flops(m, 1001)
    assert f2 - f1 == pytest.approx(16 * 4.0 * 1000 * 16 * 128)


def test_paged_bytes_count_live_tokens_at_bf16():
    m = _model("edge2-olmo1b-xlstm350m", "olmo-1b")
    per_tok = 16 * 2 * 16 * 128 * 2
    # 3 tokens generated -> 2 decode steps reading 101 and 102 tokens
    assert peaks.paged_kv_bytes(m, 100, 3) == per_tok * (101 + 102)
    x = _model("edge2-olmo1b-xlstm350m", "xlstm-350m")
    assert peaks.paged_kv_bytes(x, 100, 3) == 0


def test_forked_prefix_is_not_counted_twice():
    m = _model("edge2-olmo1b-xlstm350m", "olmo-1b")
    full = peaks.request_flops(m, 100, 100, 5)
    fork = peaks.request_flops(m, 10, 100, 5)
    assert fork + peaks.prefix_flops(m, 90) == pytest.approx(full)
