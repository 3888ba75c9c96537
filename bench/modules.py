"""Modules the benchmark finds by name, and its refusal.

A leaf of the benchmark: the harness and the plain reference both import
it, and it imports neither.
"""
from __future__ import annotations

import functools
import importlib.util
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Refused(Exception):
    """The run cannot be measured (no chip, a traffic pin that moved, a
    missing file): the harness exits non-zero and prints no result."""


@functools.lru_cache(maxsize=None)
def load_module(directory: str, name: str, what: str):
    """The module ``<directory>/<name>.py``: a metric reader, a reference
    trace generator or a reference prefetcher family, found by the name
    ``BENCHMARK.json`` or a traffic file gives it.  A missing file is
    :class:`Refused`, naming the file."""
    path = os.path.join(directory, f"{name}.py")
    if not os.path.isfile(path):
        raise Refused(f"no {what} for {name!r}: "
                      f"{os.path.relpath(path, ROOT)} is missing")
    mod_name = "bench_{}_{}".format(
        os.path.basename(directory), re.sub(r"\W", "_", name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
