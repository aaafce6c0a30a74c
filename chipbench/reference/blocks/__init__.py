"""The reference's decoder blocks, one file each, found by name.

A configuration file names its block with ``"block"`` (``"dense"`` when
absent); ``load`` finds ``<name>.py`` in the first of ``DIRS`` that holds
it. A block file declares

* ``KEYS``: each configuration key (its Hugging Face name) that the block
  follows, mapped to the program's ``ModelConfig`` field;
* ``Dims``: a ``NamedTuple`` of the block's dimensions, with
  ``Dims.from_config(config)`` reading them from those keys;
* ``init_layer(dims, key)``: one layer's initial weights for ``key``, drawn
  as the program draws its own;
* ``block(dims, p, x, f8)``: one layer on one sequence ``x`` (S, d),
  float32, every product through ``model.mm(..., f8)``;
* ``init_final_norm(dims)`` and ``final_norm(dims, w, x)``: the norm before
  the head;
* ``layer_forward_flops(dims, rows, seq, causal)``: one layer's forward
  over ``rows`` sequences, counted as ``flops.py`` counts (matrix products
  at two operations a multiply-add, elementwise work left out).

The keys that ``model.py`` itself reads (``harness.MODEL_KEYS``) belong to
no block. Nothing in a block file imports the program.
"""
from __future__ import annotations

import importlib.util
import os
import re
from types import ModuleType
from typing import Any, Dict, List

DIRS: List[str] = [os.path.dirname(os.path.abspath(__file__))]
DEFAULT = "dense"
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_loaded: Dict[str, ModuleType] = {}


def load(name: str) -> ModuleType:
    """The block file ``<name>.py``, from the first of ``DIRS`` that has
    it; each file is loaded once."""
    if not _NAME.match(name):
        raise ValueError(f"not a block name: {name!r}")
    for d in DIRS:
        path = os.path.join(d, name + ".py")
        if os.path.isfile(path):
            break
    else:
        raise KeyError(f"no block file {name}.py in {DIRS}")
    if path not in _loaded:
        spec = importlib.util.spec_from_file_location(
            "chipbench_block_" + re.sub(r"\W", "_", name), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def of(config: Dict[str, Any]) -> ModuleType:
    """The block a configuration file names."""
    return load(config.get("block", DEFAULT))
