"""Production mesh construction (assignment §MULTI-POD DRY-RUN) and the
(host, device) lattice mesh model.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state.

Two mesh families live here:

* :func:`make_production_mesh` / :func:`make_mesh` — the LM-training meshes
  (``data``/``model``/``pod`` axes) used by ``launch.dryrun``.
* :class:`MeshSpec` — the SU3 lattice's (host, device) mesh.  The paper's
  NUMA lesson (§4: data must be first-touched by the socket that will stream
  it) generalizes to a fleet as *the lattice shard must be materialized by
  the host that owns it*; ``MeshSpec`` is the object that carries that
  topology from launch config into ``core.su3.plan.build_plan`` and
  ``serve.su3.SU3Service``.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

# Axis names of the lattice (host, device) mesh.  The legacy 1-D mesh uses a
# single "sites" axis; multi-host plans shard the site dimension over BOTH of
# these (host-major), so one host's sites are contiguous — the property the
# halo model in ``distributed.sharding`` and per-host first-touch init rely on.
HOST_AXIS = "hosts"
DEVICE_AXIS = "devices"


def _mk(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests / reduced-device dry-runs / elastic re-mesh)."""
    return _mk(shape, axes)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Topology of the lattice mesh: ``hosts`` x ``devices_per_host``.

    One instance describes where lattice shards live; :func:`resolve` turns
    it into the concrete 2-D ``jax.sharding.Mesh`` a plan shards over, and
    :func:`host_submesh` yields the 1-D per-host mesh a host-local serving
    pool runs on.

    Attributes:
        hosts: number of hosts (processes / NUMA domains / pods).  ``1``
            reproduces the legacy single-host behavior exactly.
        devices_per_host: devices each host contributes.  ``0`` (default)
            infers ``len(devices) // hosts``.

    Device assignment is host-major over the device list (``jax.devices()``
    order, which in a real multi-controller run groups devices by process),
    so host ``h`` owns the contiguous block
    ``devices[h * dph : (h + 1) * dph]`` and, under the site sharding, the
    contiguous site range ``[h * S/hosts, (h + 1) * S/hosts)``.

    When the local pool has fewer devices than ``hosts * devices_per_host``
    (a laptop / single-CPU container), :func:`host_devices` falls back to
    *oversubscription*: every simulated host maps onto the head of the local
    device list.  Routing, batching, and shard math stay exactly as they
    would be on a fleet; only the physical placement collapses.  ``resolve``
    (the full 2-D mesh) accepts an explicit ``devices`` list for the same
    simulation (tests pass ``[dev] * n``).
    """

    hosts: int = 1
    devices_per_host: int = 0

    def __post_init__(self) -> None:
        if self.hosts < 1:
            raise ValueError(f"hosts must be >= 1, got {self.hosts}")
        if self.devices_per_host < 0:
            raise ValueError(
                f"devices_per_host must be >= 0 (0 = infer), got {self.devices_per_host}"
            )

    # -- concrete meshes -------------------------------------------------------

    def _dph(self, n_available: int) -> int:
        if self.devices_per_host:
            return self.devices_per_host
        return max(n_available // self.hosts, 1)

    def resolve(self, devices: list | None = None) -> jax.sharding.Mesh:
        """The concrete (hosts, devices) mesh this spec describes.

        Args:
            devices: explicit device list (simulation / tests); defaults to
                ``jax.devices()``.  Must hold at least
                ``hosts * devices_per_host`` entries.

        Returns:
            ``jax.sharding.Mesh`` of shape ``(hosts, devices_per_host)`` with
            axes ``("hosts", "devices")`` — or, for a single-host spec over
            one device row, the legacy 1-D ``("sites",)`` mesh, so
            ``MeshSpec()`` is a drop-in for ``plan.make_site_mesh()``.
        """
        devices = list(devices if devices is not None else jax.devices())
        dph = self._dph(len(devices))
        need = self.hosts * dph
        if len(devices) < need:
            raise ValueError(
                f"MeshSpec(hosts={self.hosts}, devices_per_host={dph}) needs "
                f"{need} devices, have {len(devices)}; pass an explicit device "
                f"list to simulate, or lower the spec"
            )
        if self.hosts == 1:
            return jax.sharding.Mesh(np.array(devices[:dph]), ("sites",))
        arr = np.array(devices[:need]).reshape(self.hosts, dph)
        return jax.sharding.Mesh(arr, (HOST_AXIS, DEVICE_AXIS))

    def host_devices(self, host: int, devices: list | None = None) -> list:
        """Devices owned by ``host`` (oversubscribed when the pool is short).

        Returns host ``h``'s contiguous block of the device list; on a local
        pool smaller than the spec, every host shares the head of the list
        (simulation fallback — see class docstring).
        """
        if not 0 <= host < self.hosts:
            raise ValueError(f"host {host} out of range [0, {self.hosts})")
        devices = list(devices if devices is not None else jax.devices())
        dph = self._dph(len(devices))
        if len(devices) >= self.hosts * dph:
            return devices[host * dph:(host + 1) * dph]
        return devices[:dph]

    def host_submesh(self, host: int, devices: list | None = None) -> jax.sharding.Mesh:
        """1-D ``("sites",)`` mesh over ``host``'s devices.

        This is what a host-local serving pool (one
        ``BatchedLatticeRunner`` per warm entry) plans against: work routed
        to ``host`` dispatches only on that host's devices.
        """
        return jax.sharding.Mesh(
            np.array(self.host_devices(host, devices)), ("sites",)
        )

    # -- identity --------------------------------------------------------------

    @property
    def is_multi_host(self) -> bool:
        return self.hosts > 1

    def n_devices(self, devices: list | None = None) -> int:
        devices = list(devices if devices is not None else jax.devices())
        return self.hosts * self._dph(len(devices))

    def describe(self) -> str:
        dph = self.devices_per_host or "auto"
        return f"{self.hosts}h x {dph}d"

    # -- constructors ----------------------------------------------------------

    @classmethod
    def single_host(cls) -> "MeshSpec":
        """The legacy topology: one host, all local devices."""
        return cls(hosts=1)

    @classmethod
    def simulated(cls, hosts: int, devices_per_host: int = 0) -> "MeshSpec":
        """A fake-fleet spec for tests/dryruns; identical to the constructor,
        named so call sites read as what they are."""
        return cls(hosts=hosts, devices_per_host=devices_per_host)
