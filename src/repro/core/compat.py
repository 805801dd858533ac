"""The one place the map step reaches ``shard_map``.

Everything that needs ``shard_map`` (the POP map-step backend, gradient
compression under data parallelism, tests) goes through :func:`shard_map`
here, so a JAX upgrade is a one-file change instead of a grep-the-repo
event.  ``scripts/check_imports.py`` catches the next rename at smoke
speed.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check: Optional[bool] = None, **kw: Any) -> Callable:
    """``jax.shard_map`` with ``check`` spelled as its ``check_vma``.

    POP map steps pass ``check=False``: solver constants (e.g.
    power-iteration seed vectors) are intentionally unvarying while the
    problem data varies over the POP axis.
    """
    if check is not None:
        kw["check_vma"] = check
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def device_count() -> int:
    return jax.device_count()
