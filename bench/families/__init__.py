"""The benchmark's model families: one file each, bench/families/<family>.py,
found by a configuration's `family`. A family file exports `arch_kw`,
`weights`, `layers`, `dispatches`, `row_flops` and `request_flops`
(bench/harness.py says what each is)."""
from __future__ import annotations

import functools
import importlib.util
import os

FAMILIES = os.path.dirname(os.path.abspath(__file__))


def load(path: str, name: str):
    """The module in the file at `path`, under `name`."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _family_at(path: str):
    # loaded once a process: the reference's jitted functions live there
    name = os.path.splitext(os.path.basename(path))[0]
    return load(path, f"bench_family_{name}")


def family(config: dict):
    """The file of a configuration's model family, FAMILIES/<family>.py."""
    return _family_at(os.path.join(FAMILIES, config["family"] + ".py"))
