"""Production mesh construction (deliverable e).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import
and only then builds meshes.

Axes:
  single pod : (data=16, model=16)            — 256 chips (one v5e pod slice)
  multi-pod  : (pod=2, data=16, model=16)     — 512 chips

``pod`` and ``data`` together carry data parallelism (batch sharding);
``model`` carries tensor/expert parallelism per the per-arch rules in
``repro.models.params``. The duet serving launcher additionally splits the
``model`` axis into prefill/decode sub-meshes at the Algorithm-1 ratio
(``split_duet_submeshes``) — the chip-granular analogue of the paper's SM
partitioning.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules here
    are annotations the compiler propagates, not explicit-sharding types
    (``jax.make_mesh`` defaults to ``Explicit``, under which the sharded
    embedding gather raises ``ShardingTypeError``)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2,
                   pod: Optional[int] = None) -> Mesh:
    """Small mesh over however many (host) devices the test session has.

    The requested shape is validated against ``jax.device_count()`` up
    front — ``jax.make_mesh``'s own failure surfaces as an opaque reshape
    error, while the fix (force host devices or shrink --tp/--dp) is only
    obvious from the counts."""
    if data < 1 or model < 1 or (pod is not None and pod < 1):
        raise ValueError(
            f"mesh axes must be positive, got data={data} model={model} "
            f"pod={pod}")
    need = data * model * (pod or 1)
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh shape (pod={pod}, data={data}, model={model}) needs "
            f"{need} devices but only {have} are visible; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N (before "
            "importing jax) or reduce the requested parallelism")
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def split_data_axis(mesh: Mesh) -> Tuple[Mesh, ...]:
    """Split a mesh into one TP submesh per data-parallel replica.

    The combined (pod, data) axes are carved into single-column
    ``(data=1, model=tp)`` submeshes — the cluster-router analogue of
    :func:`split_duet_submeshes`: where the duet split partitions the
    ``model`` axis between prefill and decode streams, this partitions the
    data axes between independent serving replicas. Each returned mesh owns
    a disjoint device set; together they cover the input mesh.

    Args:
        mesh: a mesh whose last axis is ``model`` (the shapes
            ``make_test_mesh``/``make_production_mesh`` build).

    Returns:
        ``dp`` meshes (``dp`` = product of the pod/data axis sizes), each
        with axes ``("data", "model")`` and shape ``(1, tp)``.

    Raises:
        ValueError: if the mesh's trailing axis is not ``model``.
    """
    if mesh.axis_names[-1] != "model":
        raise ValueError(
            "split_data_axis needs 'model' as the trailing axis, mesh has "
            f"{tuple(mesh.axis_names)}")
    model_size = mesh.shape["model"]
    devs = mesh.devices.reshape(-1, model_size)
    return tuple(Mesh(devs[i:i + 1], ("data", "model"))
                 for i in range(devs.shape[0]))


def split_duet_submeshes(mesh: Mesh, decode_chips: int):
    """Split the mesh's ``model`` axis into (prefill_mesh, decode_mesh).

    The decode sub-mesh gets ``decode_chips`` columns of the model axis, the
    prefill sub-mesh the rest — DuetServe's SM partition at chip granularity.
    Both sub-meshes keep the full data/pod axes (each data shard splits its
    model column group).
    """
    if "model" not in mesh.shape:
        raise ValueError(
            "split_duet_submeshes needs a 'model' axis, mesh has "
            f"{tuple(mesh.axis_names)}")
    model_size = mesh.shape["model"]
    if not 0 < decode_chips < model_size:
        raise ValueError(
            f"decode_chips must be in (0, {model_size}) so both sub-meshes "
            f"are non-empty, got {decode_chips}")
    devs = mesh.devices  # ndarray indexed by axis order
    model_axis = list(mesh.axis_names).index("model")
    dec = np.take(devs, range(model_size - decode_chips, model_size),
                  axis=model_axis)
    pre = np.take(devs, range(0, model_size - decode_chips), axis=model_axis)
    return (Mesh(pre, mesh.axis_names), Mesh(dec, mesh.axis_names))
