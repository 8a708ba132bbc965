"""What the benchmark knows of each architecture: one module per family.

Every other ``*.py`` file in this directory is a family module.  It
declares ``KINDS``, a dict from each layer kind it owns (the name that a
configuration's ``layer_pattern`` writes) to a ``Kind``: the layer's
seeded weight layout, its plain float32 reference and its counts for
``perfbench/peaks.py``.  A configuration selects its families by the
kinds that its ``layer_pattern`` names, and by nothing else; the shared
walks in ``weights.py``, ``reference.py`` and ``peaks.py`` look each
layer up here.

Beside ``KINDS`` a family may give model-level parts.  The shared walks
apply those of the families a configuration selects, and behave as they
would without them where none gives one:

* ``embed_scale(c)``, ``residual_scale(c)``, ``logit_scale(c)``: factors
  on the embedding, on every residual branch (``reference.residual``) and
  on the logits, read from the model's dict ``c``;
* ``extra_layout(cfg)``: top-level weight leaves beside ``embed``,
  ``blocks``, ``final_norm`` and ``lm_head``, such as meta tokens.

A family may also declare ``PARTS``: named sub-blocks, each a ``Kind``
whose ``layer`` returns the branch's output rather than the residual
stream.  ``olmo.py``'s feed-forward takes the part ``moe`` in place of
its dense MLP where the configuration states a ``moe`` group.

A kind or part declared twice, a model-level part given by two families
of one configuration, and a kind or part that no module declares are
errors that name it and this directory.
"""
from __future__ import annotations

import functools
import importlib
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Tuple

ARCH_DIR = Path(__file__).resolve().parent
WHERE = f"perfbench/arch/ ({ARCH_DIR})"


def _no_paged_reads(m: dict, itemsize: int) -> int:
    return 0


@dataclass(frozen=True)
class Kind:
    """One layer kind.

    * ``block(cfg)``: the nested leaf specs ``(shape, rule[, dtype])`` of
      one layer, from the ``ModelConfig`` (``perfbench/weights.py``);
    * ``layer(p, x, c, quant)``: the plain float32 reference of one
      layer over one sequence ``x`` [T, d_model], from the model's dict;
    * ``params(m)``: the weights one token passes through in the layer;
    * ``flops(m, ctx)``: what one token at context ``ctx`` spends beyond
      two FLOPs a weight (attention over the live, window-capped
      context; state updates);
    * ``kv_bytes(m, itemsize)``: the live-cache bytes one decode step
      reads through the paged pool per token of context.
    """
    block: Callable
    layer: Callable
    params: Callable
    flops: Callable
    kv_bytes: Callable = _no_paged_reads


@functools.lru_cache(maxsize=None)
def _registry() -> Tuple[Dict[str, tuple], Dict[str, tuple]]:
    """{kind: (module, Kind)} and {part: (module, Kind)} over every
    family module in this directory."""
    kinds: Dict[str, tuple] = {}
    parts: Dict[str, tuple] = {}
    for path in sorted(ARCH_DIR.glob("*.py")):
        if path.name.startswith("_"):
            continue
        mod = importlib.import_module(f"{__name__}.{path.stem}")
        for table, what, into in ((getattr(mod, "KINDS", {}), "layer kind",
                                   kinds),
                                  (getattr(mod, "PARTS", {}), "part", parts)):
            for name, k in table.items():
                if name in into:
                    raise ValueError(
                        f"{what} {name!r} is declared twice in "
                        f"{WHERE}: by {into[name][0].__name__} and "
                        f"by {mod.__name__}")
                into[name] = (mod, k)
    return kinds, parts


def kind(name: str) -> Kind:
    kinds = _registry()[0]
    if name not in kinds:
        raise ValueError(f"layer kind {name!r} is declared by no module in "
                         f"{WHERE}; declared there: {sorted(kinds)}")
    return kinds[name][1]


def part(name: str) -> Kind:
    parts = _registry()[1]
    if name not in parts:
        raise ValueError(f"part {name!r} is declared by no module in "
                         f"{WHERE}; declared there: {sorted(parts)}")
    return parts[name][1]


def kinds(pattern: Iterable[str]) -> List[str]:
    """The distinct kinds of ``pattern``, in order, each one known."""
    out = list(dict.fromkeys(pattern))
    for k in out:
        kind(k)
    return out


def model_part(pattern: Iterable[str], name: str) -> Optional[Callable]:
    """The model-level part ``name`` of the families ``pattern`` selects,
    or None where none gives it."""
    reg = _registry()[0]
    mods = {reg[k][0] for k in kinds(pattern)}
    given = sorted((m for m in mods if hasattr(m, name)),
                   key=lambda m: m.__name__)
    if len(given) > 1:
        raise ValueError(f"model-level part {name!r} is given by "
                         f"{[m.__name__ for m in given]}, families of one "
                         f"configuration")
    return getattr(given[0], name) if given else None
