"""One module per block family, ``bench/arch/<arch>.py``, chosen by the
``arch`` key of a configuration file's ``model`` part and found by file
name, as ``harness.load_reader`` finds metric readers.  A configuration of
a new family adds its module and its file and edits nothing here.

Each module provides, for a configuration file ``cfg`` and its ``model``
part ``m``:

* ``program_config(cfg)``: the program's ``ModelConfig`` (it imports the
  program inside the function only);
* ``shapes(m)`` and ``nest(flat)``: the weight layout, leaf name ->
  (shape, dtype, std, mean), and the program's tree of those leaves;
* ``hidden(m, params, tokens, fp8)`` and ``logits(m, params, h, fp8)``:
  the plain float32 reference at ``highest`` precision, which imports
  nothing of the program;
* ``decode_step(m, contexts)`` and ``prefill(m, prompt_len)``: (FLOPs,
  bytes) of one served step, from shapes alone.
"""
from __future__ import annotations

import functools
import importlib.util
import os
import re
from typing import Dict

DIR = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def of(m: Dict):
    """The module of the family that a ``model`` part names."""
    if "arch" not in m:
        raise ValueError("the configuration's model part names no arch")
    return load(m["arch"])


def load(name: str):
    """The module ``<DIR>/<name>.py``."""
    if not NAME.fullmatch(name):
        raise ValueError(f"arch {name!r} is not a module name")
    path = os.path.join(DIR, f"{name}.py")
    if not os.path.isfile(path):
        raise ValueError(f"no module for arch {name!r}: {path}")
    return _load(path)


@functools.lru_cache(maxsize=None)
def _load(path: str):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(f"bench_arch_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
