"""The few jax entry points this repo wraps, spelled for the installed jax
(0.9).  Every call site imports from here, so a later jax upgrade is a
one-file change.
"""
from __future__ import annotations

import contextlib
from typing import Iterable, Optional

import jax


def shard_map(
    f,
    mesh,
    in_specs,
    out_specs,
    axis_names: Optional[Iterable[str]] = None,
    check_vma: Optional[bool] = None,
):
    """``jax.shard_map``; ``axis_names`` is the set of MANUAL axes (None:
    all of the mesh's axes), ``check_vma=None`` keeps jax's default."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=True if check_vma is None else check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)


@contextlib.contextmanager
def enable_x64():
    """``with jax.enable_x64(True)``."""
    with jax.enable_x64(True):
        yield


def manual_axis_names() -> set:
    """Mesh axis names currently bound as Manual (inside a shard_map body).

    with_sharding_constraint specs must not mention these."""
    am = jax.sharding.get_abstract_mesh()
    return {
        n
        for n, t in zip(am.axis_names, am.axis_types)
        if t == jax.sharding.AxisType.Manual
    }
