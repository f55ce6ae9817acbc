"""The system under test as the benchmark builds it: the program's own
configuration of the named architecture, with the layer groups the
configuration file gives, after checking that every width in the file is
the program's. The model type's file (``archs/<model_type>.py``) says which
keys are widths (``WIDTHS``) and how the file's depth makes the groups.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from chipbench import harness


def model_config(cfg: Dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models import get_config
    arch = harness.arch(cfg["model_type"])
    base = get_config(cfg["program_arch"])
    for key, attr in arch.WIDTHS.items():
        if getattr(base, attr) != cfg[key]:
            raise ValueError(f"{cfg['program_arch']}: {attr} is "
                             f"{getattr(base, attr)!r}, the configuration's "
                             f"{key} is {cfg[key]!r}")
    return dataclasses.replace(base, groups=arch.groups(cfg))
