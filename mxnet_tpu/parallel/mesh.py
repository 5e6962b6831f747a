"""Device-mesh construction for dp/tp/sp/pp/ep parallelism.

New first-class capability (SURVEY.md §2.4): the reference scales only by data
parallelism (KVStore comm trees / ps-lite); here every strategy is a named
mesh axis consumed by `NamedSharding` rules and `shard_map` collectives:

- 'dp' — data parallel (batch axis; gradient psum rides ICI)
- 'tp' — tensor parallel (Dense/attention weight sharding)
- 'sp' — sequence/context parallel (ring attention over `ppermute`)
- 'pp' — pipeline stages (shard_map + collective_permute microbatching)
- 'ep' — expert parallel (MoE all-to-all)
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import numpy as _onp
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError

__all__ = ["make_mesh", "auto_mesh", "MeshConfig", "Mesh", "NamedSharding",
           "shard_map_nocheck", "fit_axes",
           "PartitionSpec"]

AXES = ("dp", "sp", "tp", "pp", "ep")


def fit_axes(n_devices: int, tp: int = 1, sp: int = 1, pp: int = 1,
             ep: int = 1) -> Dict[str, int]:
    """Clamp a model-axis plan to a (possibly changed) device count —
    the elastic-reform companion to `auto_mesh`: each requested model
    axis is reduced to its largest divisor compatible with the devices
    that remain (gcd), claimed in tp → sp → pp → ep order, and dp
    absorbs whatever is left.  ``fit_axes(4, tp=2)`` keeps tp=2 with
    dp=2; ``fit_axes(3, tp=2)`` degrades to tp=1, dp=3 — the mesh
    re-forms at ANY surviving device count instead of refusing."""
    import math
    out: Dict[str, int] = {}
    rem = int(n_devices)
    if rem < 1:
        raise MXNetError(f"fit_axes needs >= 1 device, got {n_devices}")
    for name, want in (("tp", tp), ("sp", sp), ("pp", pp), ("ep", ep)):
        got = math.gcd(max(int(want), 1), rem)
        out[name] = got
        rem //= got
    out["dp"] = rem
    return out


class MeshConfig:
    def __init__(self, dp: int = 1, sp: int = 1, tp: int = 1, pp: int = 1,
                 ep: int = 1):
        self.sizes = {"dp": dp, "sp": sp, "tp": tp, "pp": pp, "ep": ep}

    @property
    def n_devices(self) -> int:
        n = 1
        for v in self.sizes.values():
            n *= v
        return n

    def axis_names(self) -> Tuple[str, ...]:
        return tuple(a for a in AXES if self.sizes[a] > 1) or ("dp",)


def make_mesh(axis_sizes: Dict[str, int], devices=None) -> Mesh:
    """Build a Mesh with named axes from {'dp': 4, 'tp': 2, ...}."""
    devices = list(devices if devices is not None else jax.devices())
    names = [a for a in AXES if axis_sizes.get(a, 1) > 1]
    if not names:
        names = ["dp"]
        axis_sizes = {"dp": len(devices)}
    shape = [axis_sizes[a] for a in names]
    total = int(_onp.prod(shape))
    if total != len(devices):
        raise MXNetError(f"mesh {dict(zip(names, shape))} needs {total} "
                         f"devices, got {len(devices)}")
    arr = _onp.array(devices).reshape(shape)
    return Mesh(arr, tuple(names))


def auto_mesh(n_devices: Optional[int] = None, tp: int = 1, sp: int = 1,
              pp: int = 1, ep: int = 1, devices=None) -> Mesh:
    """Mesh with dp absorbing whatever is left after tp/sp/pp/ep."""
    devices = list(devices if devices is not None else jax.devices())
    n = n_devices or len(devices)
    denom = tp * sp * pp * ep
    if n % denom:
        raise MXNetError(f"{n} devices not divisible by tp*sp*pp*ep={denom}")
    return make_mesh({"dp": n // denom, "sp": sp, "tp": tp, "pp": pp,
                      "ep": ep}, devices[:n])


def shard_map_nocheck(fn, mesh, in_specs, out_specs):
    """`shard_map` with the vma/replication checker off: the Pallas flash
    kernel's `pallas_call` output ShapeDtypeStructs carry no `vma`
    annotation, which jax's `check_vma=True` default rejects inside a
    mapped body (the kernel would silently fall back to O(L²) reference
    attention on the SP path). Single switch point for every SP/PP
    shard_map in the package.

    TRADE-OFF (ADVICE r3): the switch is body-wide — it also silences
    the replication checker for the collectives surrounding the kernel
    call, so an out_specs/replication bug in an SP/PP body surfaces as
    wrong numerics, not a trace-time error.  jax has no narrower scope
    today; the compensating control is tests that pin numerics against
    the single-device path (tests/unittest/test_parallel.py ring/Ulysses
    equivalence, tests/dist/).  Revisit if jax grows per-region vma
    control."""
    from ..ops.pallas import per_shard

    @functools.wraps(fn)
    def body(*args):
        # arrays are per-device in here: kernels a surrounding GSPMD
        # trace switched off (ops.pallas.partitioned_by_gspmd) lower
        with per_shard():
            return fn(*args)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
