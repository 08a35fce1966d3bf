"""Model families, one module each: what the family's layers are, in the
reference and in the counts.  A configuration file's
``reference_family`` names its module, or, where it has none, its
``family`` (the program's, which the run checks).  Adding a family adds
a module here; no other file changes.

A family module gives:

* ``init(key, cfg)``: one learner's initial weights, by the recipe of
  the program's family (``jax.random`` calls in a fixed order);
* ``FIELDS``: the program's configuration fields, beyond
  ``bench.PROGRAM_FIELDS``, that must equal the file's;

and either one kind of layer, stacked ``cfg["n_layers"]`` deep in
``params["layers"]`` under RoPE (or M-RoPE) angles over
``cfg["head_dim"]``:

* ``block(lp, x, ang, cfg)``: one layer of the reference;
* ``layer_flops(cfg, seq)``: forward operations of one layer over one
  sequence, counted as ``counts`` says;
* ``layer_params(cfg)``: parameters of one layer;

or a whole stack of its own, which replaces each of those three it
stands for:

* ``stack(params, x, cfg) -> (x, extra)``: the embedded rows ``x``
  ``[batch, seq, d_model]`` through every layer, before the final norm,
  and a loss term the family adds to the mean cross-entropy (a
  router's balance loss, say); the family makes its own rotary angles;
* ``stack_flops(cfg, seq)``: forward operations of the whole stack over
  one sequence;
* ``stack_params(cfg)``: parameters of the whole stack.

Where positions carry no label, ``vision_positions(cfg, seq)``.
"""
from __future__ import annotations

import importlib
from pathlib import Path
from types import ModuleType
from typing import Dict


def get(family: str) -> ModuleType:
    try:
        return importlib.import_module(f"chipbench.families.{family}")
    except ModuleNotFoundError as e:
        raise KeyError(f"no family module chipbench/families/{family}.py"
                       ) from e


def of(cfg: Dict) -> ModuleType:
    """The module of a configuration's reference family."""
    return get(cfg.get("reference_family", cfg["family"]))


def search(directory: Path) -> None:
    """Find family modules in a checkout's ``families/`` directory as
    well, after this one (a no-op for this checkout's own)."""
    d = str(Path(directory).resolve())
    if d not in {str(Path(x).resolve()) for x in __path__}:
        __path__.append(d)
        importlib.invalidate_caches()
