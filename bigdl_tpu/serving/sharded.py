"""ShardedReplicaSet — a ReplicaSet whose replica is a MESH SLICE.

ROADMAP item 1: today a replica is exactly one device, so a model that
does not fit one chip cannot be served at all.  Here a replica slot owns
``devices_per_replica`` devices arranged as a named
:class:`~jax.sharding.Mesh` (``parallel/mesh.py``), and the replica's
params are ``device_put`` leaf-by-leaf with the
:class:`~jax.sharding.NamedSharding` the model's own ``param_specs``
opt-ins declare (``parallel/tensor_parallel.py`` —
``Linear(shard="column"/"row")``, ``MultiHeadAttention(shard=True)``;
the SNIPPETS NamedSharding weight-placement pattern: "8-chip pods to
6000-chip superclusters without changing application code").  GSPMD
inserts the collectives around the split matmuls; nothing here writes
communication by hand.

Everything else is INHERITED from :class:`~bigdl_tpu.resilience.
ReplicaSet`: least-queue-depth routing, health/quarantine/failover,
elastic ``set_replica_count`` (a grown mesh-slice replica AOT-warms its
bucket ladder off the routing path), ``stats()`` aggregation, and the
``submit()``-shaped contract — so ``FrontendServer.add_backend``,
:class:`~bigdl_tpu.frontend.HotCutover`, the
:class:`~bigdl_tpu.frontend.ReplicaAutoscaler` and ``/metrics`` all work
at mesh-slice granularity with zero frontend changes (the frontend's
``isinstance(backend, ReplicaSet)`` dispatch sees this subclass).

Device partitioning: the device list is cut into consecutive groups of
``devices_per_replica``; slot ``ix`` takes group ``ix % n_groups``, so —
like the base class — more replicas than device groups is legal
(emulated replicas share a group round-robin, the CPU-host test rig).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

from bigdl_tpu.resilience.health import ReplicaHealth
from bigdl_tpu.resilience.replica_set import ReplicaSet
from bigdl_tpu.serving.service import InferenceService


class ShardedReplicaSet(ReplicaSet):
    """:class:`ReplicaSet` with N-device mesh-slice replicas.

    Parameters beyond the base class:

    - ``devices_per_replica``: devices per slot (the mesh-slice size).
      ``devices`` must supply at least one full group.
    - ``mesh_axes``: axis-name → size dict for the per-slot mesh
      (default ``{"model": devices_per_replica}`` — pure tensor
      parallelism).  Axis sizes must multiply to
      ``devices_per_replica``; unnamed axes default to 1.  Axis names
      follow ``parallel/mesh.py`` (``data``/``model``/``seq``/``pipe``).

    ``n_replicas`` defaults to the number of COMPLETE device groups
    (``len(devices) // devices_per_replica``), not the device count.
    """

    def __init__(self, model, params=None, state=None, *,
                 devices_per_replica: int = 2,
                 mesh_axes: Optional[Dict[str, int]] = None,
                 n_replicas: Optional[int] = None,
                 devices: Optional[Sequence] = None, **kw):
        import jax
        if devices is None:
            devices = jax.local_devices()
        devices = list(devices)
        dpr = int(devices_per_replica)
        if dpr < 1:
            raise ValueError(f"devices_per_replica must be >= 1: {dpr}")
        n_groups = len(devices) // dpr
        if n_groups < 1:
            raise ValueError(
                f"need at least {dpr} devices for one mesh-slice "
                f"replica, have {len(devices)}")
        axes = dict(mesh_axes) if mesh_axes else {"model": dpr}
        bad = set(axes) - {"data", "model", "seq", "pipe"}
        if bad:
            raise ValueError(f"unknown mesh axes {sorted(bad)}")
        size = 1
        for v in axes.values():
            size *= int(v)
        if size != dpr:
            raise ValueError(
                f"mesh axes {axes} multiply to {size}, need "
                f"devices_per_replica={dpr}")
        # set BEFORE super().__init__ — the base constructor calls
        # _build_replica (overridden below) for every initial slot
        self.devices_per_replica = dpr
        self._mesh_axes = axes
        self._groups = [devices[g * dpr:(g + 1) * dpr]
                        for g in range(n_groups)]
        if n_replicas is None:
            n_replicas = n_groups
        super().__init__(model, params, state, n_replicas=n_replicas,
                         devices=devices, **kw)

    # ---------------------------------------------------- replica build
    def replica_mesh(self, ix: int):
        """The (already-built) mesh of slot ``ix``'s service, or a fresh
        one for a not-yet-built slot — introspection surface for tests
        and ops tooling."""
        svc = self._replicas[ix] if ix < len(self._replicas) else None
        mesh = getattr(svc, "_mesh", None)
        return mesh if mesh is not None else self._slot_mesh(ix)

    def _slot_mesh(self, ix: int):
        from bigdl_tpu.parallel.mesh import create_mesh
        group = self._groups[ix % len(self._groups)]
        ax = self._mesh_axes
        return create_mesh(data=ax.get("data", 1),
                           model=ax.get("model", 1),
                           seq=ax.get("seq", 1),
                           pipe=ax.get("pipe", 1), devices=group)

    def _build_replica(self, ix: int, input_spec):
        """Mesh-slice twin of the base builder: instead of committing
        params onto ONE device, build slot ``ix``'s named mesh over its
        device group and ``device_put`` every param leaf with the
        NamedSharding its module declared (replicated ``P()`` for
        non-opt-ins).  The replica's jit then follows its params'
        shardings — GSPMD compiles the collectives into the bucket
        executables during the SAME off-path AOT warmup the base class
        does, so a grown mesh-slice replica never serves a compile (or
        collective-layout) stall."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from bigdl_tpu.parallel.tensor_parallel import build_param_specs
        mesh = self._slot_mesh(ix)
        specs = build_param_specs(self._model, self._base_params)
        p_i = jax.tree_util.tree_map(
            lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)),
            self._base_params, specs)
        s_i = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P())),
            self._base_state)
        svc = InferenceService(
            self._model, p_i, s_i, input_spec=input_spec,
            name=f"{self.name}/r{ix}",
            start=self._started, fault_injector=self._faults,
            tracer=self.tracer,
            request_tracing=self._request_tracing,
            priority_fn=self._priority_fn, **self._service_kw)
        svc._fault_replica = ix
        svc._mesh = mesh  # introspection (replica_mesh, tests)
        health = ReplicaHealth(ix, policy=self._policy,
                               registry=self.registry,
                               recorder=self._flight)
        return svc, health
