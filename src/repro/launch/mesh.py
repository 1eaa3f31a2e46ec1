"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

  single pod:  (16, 16)        axes ('data', 'model')   = 256 chips
  multi pod:   (2, 16, 16)     axes ('pod', 'data', 'model') = 512 chips
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence[jax.Device]] = None,
              ) -> jax.sharding.Mesh:
    """jax.make_mesh over the first prod(shape) devices (the dry-run
    forces 512 host devices; the single-pod mesh uses the first 256).

    Every axis is Auto: sharding is propagated by GSPMD from the
    argument shardings and the ``shard`` constraints, as the model code
    assumes (Explicit axes would make mixed-sharding ops such as the
    embedding gather and the DSSP ring update type errors)."""
    need = math.prod(shape)
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {need} devices, have {len(devs)} — "
            "run under dryrun.py (it forces 512 host devices) or shrink "
            "the mesh")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devs[:need])


def elastic_mesh(n_devices: int, *, model_parallel: int = 16,
                 axes: Tuple[str, str] = ("data", "model"),
                 ) -> jax.sharding.Mesh:
    """Largest (data, model) mesh that fits ``n_devices`` — used by the
    elastic-scaling path after node loss (launch/elastic.py)."""
    mp = math.gcd(model_parallel, n_devices)
    dp = n_devices // mp
    return make_mesh((dp, mp), axes, devices=jax.devices()[:dp * mp])
