"""The jax entry points the mesh and conformance code share.

``shard_map`` and ``enable_x64`` are jax's own (``jax.shard_map`` with
``check_vma``, ``jax.enable_x64``); callers import them from here so the
spelling lives in one place.

Also home to ``has_ici()`` — whether cross-device collectives ride a real
chip interconnect (the partial-merge path in parallel/mesh.py routes on
it: psum over ICI when present, host-side ops/agg.reduce_phase when not).
"""
from __future__ import annotations

import jax

__all__ = ["shard_map", "enable_x64", "has_ici"]

enable_x64 = jax.enable_x64


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    """``jax.shard_map``; check_vma=None leaves jax's default in place."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def has_ici() -> bool:
    """True when same-host collectives ride a chip interconnect.  Host
    platforms (cpu) emulate collectives through host memory — there a
    plain host-side partial merge is both faster and deterministic, so
    parallel/mesh.py's partial-merge helper falls back to
    ops/agg.reduce_phase semantics."""
    return jax.default_backend() == "tpu"
