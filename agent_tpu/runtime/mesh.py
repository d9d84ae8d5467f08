"""Mesh construction over the canonical ``(dp, tp, sp)`` axes.

Axis vocabulary (fixed across the framework so every sharding spec and
collective agrees):

- ``dp`` — data parallelism: batch rows sharded, params replicated.
- ``tp`` — tensor/model parallelism: heads and MLP hidden sharded.
- ``sp`` — sequence/context parallelism: the sequence axis for ring attention
  (SURVEY.md §5.7).

An expert axis (``ep``) is deliberately *not* pre-created but nothing below
assumes three axes — :func:`build_mesh` takes any ordered axis dict, so an MoE
model can build its own mesh (SURVEY.md §2.8: "mesh design must not preclude
it").

The reference had no mesh — its device model was one Edge TPU behind one
interpreter (reference ``ops/_tpu_runtime.py:34-63``). The mesh shape here comes
from ``DeviceConfig.mesh_shape`` (``MESH_SHAPE="dp=4,tp=2"``) or is derived from
the device count (everything on ``dp`` — the right default for the map-style ops
this swarm runs).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

# Canonical axis order. dp outermost: DCN/ICI-friendliest for pure-data work,
# and the axis most collectives (psum of partials) ride.
AXES: Tuple[str, ...] = ("dp", "tp", "sp")


@dataclass(frozen=True)
class MeshSpec:
    """A validated mesh shape: ordered axis name → size, covering all devices."""

    axes: Tuple[Tuple[str, int], ...] = field(default_factory=tuple)

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.axes)

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(s for _, s in self.axes)

    @property
    def n_devices(self) -> int:
        n = 1
        for _, s in self.axes:
            n *= s
        return n

    @staticmethod
    def resolve(n_devices: int, shape: Optional[Dict[str, int]] = None) -> "MeshSpec":
        """Fill a possibly-partial shape dict into a full spec over n_devices.

        Unknown sizes (axes absent from ``shape``) default to 1, except ``dp``
        which absorbs every device not claimed by other axes. A shape that does
        not divide the device count is an error — silent truncation would strand
        chips.
        """
        shape = dict(shape or {})
        for name, size in shape.items():
            if not isinstance(size, int) or size <= 0:
                raise ValueError(f"mesh axis {name!r} must be a positive int, got {size!r}")
        extra = [n for n in shape if n not in AXES]
        names = AXES + tuple(extra)  # unknown axes appended innermost
        claimed = 1
        for n in names:
            if n != "dp" and n in shape:
                claimed *= shape[n]
        if n_devices % claimed:
            raise ValueError(
                f"mesh shape {shape} claims {claimed} devices per dp-slice but "
                f"{n_devices} devices are available (not divisible)"
            )
        dp = shape.get("dp", n_devices // claimed)
        sizes = {**{n: 1 for n in names}, **shape, "dp": dp}
        total = 1
        for n in names:
            total *= sizes[n]
        if total != n_devices:
            raise ValueError(
                f"mesh shape {shape} covers {total} devices, have {n_devices}"
            )
        return MeshSpec(axes=tuple((n, sizes[n]) for n in names))


def build_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    shape: Optional[Dict[str, int]] = None,
) -> Mesh:
    """Build a :class:`jax.sharding.Mesh` over ``devices`` with spec ``shape``.

    Device order is kept as given, with one exception: on several TPU chips
    an axis that spans ALL of them is laid out by
    ``mesh_utils.create_device_mesh``, which knows the chips' coordinates.
    Collectives on such an axis walk a ring, and list order is not one — on
    a 2x2 host ``jax.devices()`` goes 0→1→2→3 while the physical ring is
    0→1→3→2 (1→2 and 3→0 are diagonals, two hops). A mesh with several
    real axes keeps list order: on a 2x2 host that already puts every
    axis's pairs on ICI neighbours, where the ring order would put one
    axis's pairs on the diagonals.
    """
    if devices is None:
        devices = jax.devices()
    spec = MeshSpec.resolve(len(devices), shape)
    if (
        len(devices) > 1
        and devices[0].platform == "tpu"
        and max(spec.sizes) == len(devices)
    ):
        from jax.experimental import mesh_utils

        grid = mesh_utils.create_device_mesh(spec.sizes, list(devices))
    else:
        grid = np.asarray(devices, dtype=object).reshape(spec.sizes)
    return Mesh(grid, spec.names)
