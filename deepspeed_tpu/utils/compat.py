"""The two jax spellings every manual-region call site goes through.

One installation (jax 0.9): ``jax.shard_map`` and ``lax.axis_size`` exist, so
these are plain pass-throughs kept for their call sites' sake — they fix the
defaults this codebase wants (manual over every mesh axis unless
``axis_names`` says otherwise; no varying-manual-axes check).
"""

from __future__ import annotations

import jax
from jax import lax

__all__ = ["axis_size_compat", "shard_map_compat"]


def axis_size_compat(axis_name):
    """Size of a manual mesh axis, as a concrete int inside the region."""
    return lax.axis_size(axis_name)


def shard_map_compat(f, mesh, in_specs, out_specs, axis_names=None,
                     check_vma=False):
    """``jax.shard_map``; ``axis_names`` is the set of MANUAL axes, omitted
    means manual over every mesh axis."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=axis_names or set(mesh.axis_names),
                         check_vma=check_vma)
