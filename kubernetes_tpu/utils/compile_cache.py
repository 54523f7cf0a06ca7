"""Where JAX's persistent compilation cache lives.

A fresh process on the chip otherwise pays every compile cold (the
pallas scan, the XLA scan, each session pod bucket and scatter width).
The cache's directory is part of its key, so it must not move between
runs: an operator places it with ``JAX_COMPILATION_CACHE_DIR``, and
without one it sits at a fixed path inside the checkout. Entry points
(``chip_smoke.py``, ``bench.py``, ``hyperkube``) call
:func:`place_compile_cache` once at start; importing the package never
does, and the test run turns the cache off (``tests/conftest.py``).
"""

from __future__ import annotations

import os

#: The in-checkout default (listed in .gitignore).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it; call before JAX is imported. An operator's
    ``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; otherwise the
    fixed in-checkout path goes into the environment, where JAX reads
    it at import and the daemons this process starts inherit it.
    Creates nothing: JAX makes the directory on first write."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_DIR
    return path
