"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.

  single-pod: (data=16, model=16)           — 256 chips (one v5e pod)
  multi-pod:  (pod=2, data=16, model=16)    — 512 chips (2 pods)

'model' is the latency-critical axis (TP / EP / kv-sequence / graph shards:
everything that communicates per-step stays on intra-pod ICI); 'data' is
per-pod data parallelism; 'pod' carries only the once-per-step gradient
all-reduce (DCN-tolerant) — the paper's "walk never crosses machines"
principle lifted to pod scope.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: shardings propagate through jit and
    shard_map names its mesh explicitly (the default Explicit axes would
    put every array's sharding into its type)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model")) -> Mesh:
    """Small mesh over whatever devices this host actually has (tests)."""
    n = len(jax.devices())
    if shape is None:
        a = 1
        while (a * 2) * (a * 2) <= n or a * 2 * a <= n:
            if (a * 2) * a <= n:
                a *= 2
            else:
                break
        shape = (max(n // a, 1), a) if a <= n else (1, 1)
    return make_mesh(shape, axes)


def data_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def n_chips(mesh: Mesh) -> int:
    return mesh.devices.size
