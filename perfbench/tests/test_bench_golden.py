"""What the benchmark knows of each architecture, held to a snapshot.

``data/golden_arch.json`` was written by ``snapshot`` on the tree before
the per-family modules of ``perfbench/arch/`` existed.  For the tiny
models and for every node of the published configuration files it holds
the weight layout (leaf paths, shapes, rules, dtypes, flatten order) and
the counts of ``perfbench/peaks.py``; for the tiny models also a digest
of every seeded weight array and of the plain reference's logits, with
and without the float8 control.  Moving any of them moves a limit, a
metric or the weights a seed gives, so each compares exactly.

    PYTHONPATH=src JAX_PLATFORMS=cpu python3 -m perfbench.tests.test_bench_golden

rewrites the snapshot from the tree as it stands.
"""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench.tests.tiny import TINY_MODELS

HERE = Path(__file__).resolve().parent
DATA = HERE / "data" / "golden_arch.json"
CONFIGS = HERE.parent / "configs"
PUBLISHED = ("edge2-olmo1b-xlstm350m", "edge3-olmo1b-xlstm350m-hymba1.5b")
CTX = (1, 64, 1056, 1120)
SEED = 1234
T = 80                          # longer than the tiny hymba's 48-token window


def models() -> dict:
    out = {f"tiny/{k}": m for k, m in TINY_MODELS.items()}
    for name in PUBLISHED:
        cfg = json.loads((CONFIGS / f"{name}.json").read_text())
        for node in cfg["nodes"]:
            out[f"{name}/{node['arch']}"] = node["model"]
    return out


def _digest(a) -> str:
    a = np.asarray(a)
    return f"{a.dtype}{list(a.shape)}:" + hashlib.sha256(
        a.tobytes()).hexdigest()


def layout_of(m: dict) -> list:
    import jax
    from perfbench import spec, weights
    lay = weights.layout(spec.model_config({"model": m}))
    flat, _ = jax.tree_util.tree_flatten_with_path(
        lay, is_leaf=weights._is_leaf)
    return [[jax.tree_util.keystr(p), list(s[0]), s[1],
             s[2] if len(s) > 2 else None] for p, s in flat]


def counts_of(m: dict) -> dict:
    from perfbench import peaks
    return {"matmul_params": peaks.matmul_params(m),
            "token_flops": [peaks.token_flops(m, c) for c in CTX],
            "paged_kv_bytes": peaks.paged_kv_bytes(m, 1050, 32)}


def arrays_of(m: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from perfbench import reference, spec, weights
    params = weights.make_params(spec.model_config({"model": m}), SEED)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        5, m["vocab_size"], T), jnp.int32)
    return {"params": [_digest(x) for x in jax.tree.leaves(params)],
            "logits": _digest(reference.logits(params, toks, m)),
            "logits_fp8": _digest(reference.logits(params, toks, m,
                                                   quant="fp8"))}


def snapshot() -> dict:
    out = {}
    for case, m in models().items():
        out[case] = {"layout": layout_of(m), **counts_of(m)}
        if case.startswith("tiny/"):
            out[case].update(arrays_of(m))
    # through JSON, so a fresh reading compares as the stored one does
    return json.loads(json.dumps(out))


def _stored(case: str) -> dict:
    return json.loads(DATA.read_text())[case]


def _fresh(value):
    return json.loads(json.dumps(value))


CASES = sorted(models())
TINY = [c for c in CASES if c.startswith("tiny/")]


@pytest.mark.parametrize("case", CASES)
def test_layout_unchanged(case):
    assert _fresh(layout_of(models()[case])) == _stored(case)["layout"]


@pytest.mark.parametrize("case", CASES)
def test_counts_unchanged(case):
    want = _stored(case)
    got = _fresh(counts_of(models()[case]))
    assert got == {k: want[k] for k in got}


@pytest.mark.parametrize("case", TINY)
def test_weights_and_logits_bit_for_bit(case):
    want = _stored(case)
    got = arrays_of(models()[case])
    assert got["params"] == want["params"]
    assert got["logits"] == want["logits"]
    assert got["logits_fp8"] == want["logits_fp8"]


if __name__ == "__main__":
    DATA.write_text(json.dumps(snapshot(), indent=1) + "\n")
    print(f"wrote {DATA}")
