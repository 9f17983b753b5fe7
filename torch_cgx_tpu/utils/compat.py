"""The JAX surface this codebase is written against, in one place.

One installation is supported — jax 0.9.x as pinned in ``pyproject.toml``
— so these are the public ``jax`` entry points themselves (``jax.shard_map``
with ``axis_names``/``check_vma``, ``jax.set_mesh``,
``jax.distributed.is_initialized``, ``jax.lax.axis_size``). Call sites keep
importing from here so that the next JAX rename is a one-file change.
"""

from __future__ import annotations

from typing import Optional

import jax

set_mesh = jax.set_mesh
axis_size = jax.lax.axis_size


def shard_map(
    f,
    *,
    mesh,
    in_specs,
    out_specs,
    axis_names=None,
    check_vma: Optional[bool] = None,
):
    """``jax.shard_map`` with ``axis_names``/``check_vma`` passed only when
    the caller set them (so JAX's own defaults stay JAX's)."""
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kw
    )


def distributed_is_initialized() -> bool:
    return bool(jax.distributed.is_initialized())
