"""The program's model for a configuration file.

The file uses the published ``config.json`` key names; its family's
``program_config`` sets each size the program runs from it, and refuses a
key the program cannot honour rather than run differently from what the
file states.
"""
from __future__ import annotations


def build(family, conf: dict):
    from repro.models import build_model
    return build_model(family.program_config(conf))
