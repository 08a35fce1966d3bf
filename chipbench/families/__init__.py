"""Model families, one module each, found by a configuration file's
``family``: what the family's layer is, in the reference and in the
counts.  Adding a family adds a module here; no other file changes.

A family module gives:

* ``init(key, cfg)``: one learner's initial weights, by the recipe of
  the program's family (``jax.random`` calls in a fixed order);
* ``block(lp, x, ang, cfg)``: one layer of the reference;
* ``layer_flops(cfg, seq)``: forward operations of one layer over one
  sequence, counted as ``counts`` says;
* ``layer_params(cfg)``: parameters of one layer;
* ``FIELDS``: the program's configuration fields, beyond
  ``bench.PROGRAM_FIELDS``, that must equal the file's;

and, where positions carry no label, ``vision_positions(cfg, seq)``.
"""
from __future__ import annotations

import importlib
from types import ModuleType


def get(family: str) -> ModuleType:
    try:
        return importlib.import_module(f"chipbench.families.{family}")
    except ModuleNotFoundError as e:
        raise KeyError(f"no family module chipbench/families/{family}.py"
                       ) from e
