"""The chip benchmark: harness, traffic, reference, trace reduction."""

import functools
import importlib.util
from pathlib import Path


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """A file of the benchmark (a metric's reader, an arrival process, a
    reference), loaded once per process."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
