"""The plain references against the serving model's own forward pass, at
a small size on the CPU, on the benchmark's seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference, spec, weights
from perfbench.tests.tiny import TINY_MODELS


@pytest.mark.parametrize("arch", ["attn", "xlstm", "hymba"])
def test_reference_matches_model_forward(arch):
    from repro.models import Model
    m = dict(TINY_MODELS[arch])
    cfg = spec.model_config({"model": m})
    params = weights.make_params(cfg, 1234)
    shapes = jax.eval_shape(lambda k: Model(cfg).init_params(k, max_seq=96),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(shapes) == jax.tree.structure(params)
    T = 80                       # longer than hymba's 48-token window
    toks = np.random.default_rng(0).integers(5, m["vocab_size"], T)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        got, _ = Model(cfg).forward(
            params, {"tokens": jnp.asarray(toks)[None], "positions": pos})
    want = reference.logits(params, jnp.asarray(toks, jnp.int32), m)
    got = np.asarray(got[0], np.float64)
    want = np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale


def test_fp8_control_departs_from_reference():
    m = dict(TINY_MODELS["attn"])
    cfg = spec.model_config({"model": m})
    params = weights.make_params(cfg, 7)
    toks = jnp.asarray(np.arange(5, 69), jnp.int32)
    a = np.asarray(reference.logits(params, toks, m))
    b = np.asarray(reference.logits(params, toks, m, quant="fp8"))
    assert np.abs(a - b).max() > 1e-2 * np.abs(a).max()
