"""Production mesh construction (spec: MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module-level constant — importing this module never touches jax
device state.  Callers (dryrun.py) are responsible for setting
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
  """`jax.make_mesh` with Auto axes: the programs place data through
  `NamedSharding` specs and let XLA propagate the rest, which the Explicit
  axes `make_mesh` builds by default would reject at the first gather."""
  return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
  shape = (2, 16, 16) if multi_pod else (16, 16)
  axes = ("pod", "data", "model") if multi_pod else ("data", "model")
  return _auto_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: int | None = None):
  """Mesh over whatever devices exist (tests / CPU smoke / --mesh-model N).

  Axis sizes must tile the device count exactly: the old `data = n // model`
  silently built an (n//model, model) mesh that *dropped* devices whenever
  `model` did not divide n (or, with an explicit `data`, let `make_mesh`
  fail deep inside jax with an opaque reshape error).  Both are now loud,
  named errors at the call site.
  """
  n = len(jax.devices())
  if model < 1:
    raise ValueError(
        f"mesh model axis must be >= 1, got {model}; pass --mesh-model N "
        f"with N >= 1 (N=1 serves unsharded)")
  if n % model != 0:
    raise ValueError(
        f"model axis size {model} does not divide the device count {n}; "
        f"pass --mesh-model with a divisor of {n}, or force more host "
        f"devices via XLA_FLAGS=--xla_force_host_platform_device_count=N "
        f"(shard redundancy does not relax this: --shard-redundancy "
        f"host-mirror protects KV pages, it cannot invent devices)")
  if data is None:
    data = n // model
  if data * model != n:
    raise ValueError(
        f"mesh axes (data={data}, model={model}) cover {data * model} "
        f"devices but {n} exist; axis sizes must tile the device count "
        f"exactly — adjust --mesh-model (and the data axis) so "
        f"data * model == {n}")
  return _auto_mesh((data, model), ("data", "model"))


def model_axis_size(mesh) -> int:
  """Size of the mesh's `model` axis (1 when the axis is absent)."""
  return int(dict(mesh.shape).get("model", 1))
