"""A configuration's architecture family, found by name.

A configuration file, ``configs/<name>.json``, may name its family under
``"harness_family"``; without the key it is ``dense``.  The family is
the module ``archs/<family>.py``, and everything the harness does that
depends on the architecture goes through it:

- ``spec(raw, name)``: a frozen, hashable sizes object read from the
  file's dict, with at least ``name``, ``layers``, ``vocab``,
  ``program_arch`` and ``family``, the family's own name;
- ``model_config(spec)``: the program's ``ModelConfig``;
- ``program_params(spec, seed)``: the serving engine's parameter tree,
  made on the device in one call;
- ``gaps(spec, seed, seqs, served_from, control=False)``: the float32
  reference's gap of every served token (and, with ``control``, that of
  the token the reference's lower-precision control puts first), as
  ``reference.gaps`` has it;
- optionally ``build_engine(params, cfg, eng, seed)``; without it,
  ``system.build_engine``.

A new family is a new file under ``archs/`` and configurations that
name it; no file of the harness changes.  Both are looked for under each
directory of ``ROOTS`` in turn.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOTS = [HERE]
DEFAULT = "dense"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_-]{0,63}$")

_loaded = {}


def _find(kind: str, filename: str, what: str) -> str:
    looked = [os.path.join(root, kind, filename) for root in ROOTS]
    for path in looked:
        if os.path.isfile(path):
            return path
    raise ValueError(f"{what}: no file " + " or ".join(looked))


def load(name: str):
    """The family module ``archs/<name>.py``, loaded once."""
    if not NAME.match(name):
        raise ValueError(f"bad harness family name {name!r}")
    path = _find("archs", f"{name}.py", f"unknown harness family {name!r}")
    if path not in _loaded:
        mod_spec = importlib.util.spec_from_file_location(
            "chipbench_arch_" + name.replace("-", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        # a dataclass looks its module up in sys.modules while it is made
        sys.modules[mod_spec.name] = mod
        mod_spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def load_config(name: str):
    """The sizes of configuration ``name``, read by its family."""
    with open(_find("configs", f"{name}.json",
                    f"unknown configuration {name!r}")) as f:
        raw = json.load(f)
    return load(raw.get("harness_family", DEFAULT)).spec(raw, name)
