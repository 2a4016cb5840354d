"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run launcher must set
``xla_force_host_platform_device_count`` before any jax initialization).

Mesh axes:
  * ``search`` — whole-search data parallelism: the leading batch axis of
                the vmapped DSE stack (``core.search.batched_search`` /
                ``core.ga.run_ga_batched``) shards over it — one mesh slice
                per independent GA (seed or workload set).
  * ``pod``   — slow DCN-class axis between pods (multi-pod only).  Only the
                gradient all-reduce (optionally compressed) crosses it.
  * ``data``  — intra-pod FSDP/ZeRO + batch parallelism; the DSE population
                axis shards over it (``core.distributed``).
  * ``model`` — Megatron-style tensor/expert/sequence parallelism.

``make_search_mesh`` builds the 2-D ``(search, data)`` layout used by the
sharded search drivers.  Every mesh here declares its axes ``Auto``
(GSPMD propagates shardings from the committed inputs), the mode the
search stack is written for; ``jax.make_mesh`` would otherwise default
to ``Explicit`` axes.  Every constructor degrades gracefully when the
host exposes fewer devices than requested (axis sizes clamp to the device
budget, down to 1 on a single-device host), so tests and benches run
unchanged from laptops to pods.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto(axes: Tuple[str, ...]) -> Tuple[AxisType, ...]:
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False, searches: int = 1) -> Mesh:
    """16x16 pod (or 2x16x16 multi-pod) mesh; ``searches > 1`` prepends a
    ``search`` axis for fleet-scale DSE (searches x 16 x 16 devices)."""
    shape: Tuple[int, ...] = (2, 16, 16) if multi_pod else (16, 16)
    axes: Tuple[str, ...] = ("pod", "data", "model") if multi_pod else ("data", "model")
    if searches > 1:
        shape = (searches,) + shape
        axes = ("search",) + axes
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh over however many devices exist (tests, elasticity)."""
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def _fit_axis(requested: int, remaining: int) -> int:
    """Axis size clamped to the remaining device budget — the graceful-
    degradation rule shared by every mesh constructor.  Non-divisor sizes
    are fine (the constructors slice exactly ``prod(shape)`` devices), so a
    request is honored verbatim whenever it fits."""
    return max(1, min(int(requested), remaining))


def make_test_mesh(data: int = 1, model: int = 1, search: int = 1) -> Mesh:
    """Tiny mesh over the devices actually present.

    Axis sizes clamp to the device budget (down to 1) instead of asserting,
    so a ``search=8`` request degrades to ``search=1`` on a single-device
    CPU host and the same test runs on the fake-8-device CI leg unchanged.
    Returns a ``(search, data, model)`` mesh when ``search`` is requested
    (> 1), else the historical ``(data, model)`` layout.
    """
    n = len(jax.devices())
    sizes = {}
    remaining = n
    for name, req in (("search", search), ("data", data), ("model", model)):
        sizes[name] = _fit_axis(req, remaining)
        remaining //= sizes[name]
    if search > 1:
        shape = (sizes["search"], sizes["data"], sizes["model"])
        axes: Tuple[str, ...] = ("search", "data", "model")
    else:
        shape = (sizes["data"], sizes["model"])
        axes = ("data", "model")
    devs = np.asarray(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, axes, axis_types=_auto(axes))


def make_search_mesh(
    searches: Optional[int] = None, pop: Optional[int] = None
) -> Mesh:
    """2-D ``(search, data)`` mesh for the sharded batched search stack.

    ``searches`` shards the leading batch axis (independent GAs), ``pop``
    shards each GA's population.  Defaults: all devices on ``search``
    (``pop=1``) — hundreds of independent searches per launch is the
    fleet-scale win (ROADMAP).  Sizes clamp to the available devices.
    """
    n = len(jax.devices())
    if searches is None and pop is None:
        searches, pop = n, 1
    elif searches is None:
        pop = _fit_axis(pop, n)
        searches = n // pop
    elif pop is None:
        searches = _fit_axis(searches, n)
        pop = n // searches
    else:
        searches = _fit_axis(searches, n)
        pop = _fit_axis(pop, n // searches)
    devs = np.asarray(jax.devices()[: searches * pop]).reshape(searches, pop)
    return Mesh(devs, ("search", "data"), axis_types=_auto(("search", "data")))


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    """``{axis_name: size}`` in mesh order (invariant-checked in tests)."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def describe(mesh: Mesh) -> str:
    return "x".join(
        f"{a}={s}" for a, s in zip(mesh.axis_names, mesh.devices.shape)
    )
