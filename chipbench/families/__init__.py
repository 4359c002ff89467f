"""Plain references and FLOP counts, one module per model family, found
by the configuration's ``family``."""
from __future__ import annotations

from importlib import import_module


def family(name: str):
    return import_module(f".{name}", __package__)
