"""Model families of the benchmark's configurations, one module each.

``bench/families/<model_type>.py`` maps a configuration file whose
``model_type`` names it onto the serving program and the benchmark: its
``ModelConfig`` (``model_config``), the weight tree (``SIZE_KEYS``,
``shapes``, ``leaf``) and the shape counts ``bench/flops.py`` reads
(``layer_weights``, ``head_weights``, ``attention_flops_per_token``,
``kv_bytes_per_token``, ``weight_bytes``). A new family is a new file.
"""

from __future__ import annotations

import importlib.util
import os

FAMILY_DIR = os.path.dirname(os.path.abspath(__file__))
_LOADED: dict = {}


def of(hf: dict):
    """The family module of configuration ``hf`` (by its ``model_type``)."""
    name = str(hf.get("model_type"))
    if name not in _LOADED:
        path = os.path.join(FAMILY_DIR, name + ".py")
        if not os.path.isfile(path):
            raise ValueError(f"model_type {name!r} has no family file: "
                             f"looked for {path}")
        sp = importlib.util.spec_from_file_location(
            f"bench.families.{name}", path)
        mod = importlib.util.module_from_spec(sp)
        sp.loader.exec_module(mod)
        _LOADED[name] = mod
    return _LOADED[name]
