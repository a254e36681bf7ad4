"""Where compiled programs live, and how many a window of work compiles.

``use_persistent_cache`` is called from the ``main()`` of each entry point
(never at import).  ``count_compiles`` is what a served window's
"nothing compiles after prewarm" check reads, and ``has_kernel`` what a
"the Pallas kernel is in the program" check reads.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Iterator, List

import jax
from jax import monitoring

# the checkout's root: src/repro/launch/compiles.py -> parents[3]
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# fired once per program lowered to MLIR, whether the persistent cache then
# supplies the executable or the backend compiles it
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def use_persistent_cache() -> str:
    """Keep JAX's persistent compilation cache in a fixed directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    the cache is left to JAX.  Otherwise the cache goes to ``.jax_cache``
    at the checkout's root: a fixed path, since the path is part of what
    a cache entry is found by.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


@contextlib.contextmanager
def count_compiles() -> Iterator[List[str]]:
    """Collect the name of every program JAX lowers inside the block.

    A jitted call at a new shape, and an eager op at a shape it has not
    run before, each lower one program; a warm call lowers none.
    """
    names: List[str] = []

    def listener(event: str, duration: float, **kw) -> None:
        if event == _LOWER_EVENT:
            names.append(str(kw.get("fun_name", "?")))

    monitoring.register_event_duration_secs_listener(listener)
    try:
        yield names
    finally:
        monitoring.unregister_event_duration_listener(listener)


def has_kernel(hlo_text: str, name: str) -> bool:
    """Whether compiled TPU HLO text holds the Pallas kernel ``name``: a
    Pallas TPU kernel is a ``tpu_custom_call`` named after the kernel."""
    return any("tpu_custom_call" in line and name in line
               for line in hlo_text.splitlines())
