"""First-class description of the (pod, stage, data) device topology.

Every entry point used to hand-roll its own mesh: `SpmdEngine` built a
single-host (stage, data) mesh, `dryrun_pipeline.py` a fake 512-chip
(pod, stage, data) mesh, and the benchmarks a third variant. `Topology` is
the single owner of that decision: it names the axes, builds the mesh
(`jax.make_mesh` with Auto axis types), derives the
PartitionSpecs for stage-stacked parameters and microbatched token streams,
and tells the data pipeline how many host shards the global batch splits
into. Everything downstream — the tick schedules' gradient reductions, the
engine's batch validation, the sharded checkpointer's shard count — reads
the same object instead of re-deriving axis names.

Axis layout (pod-major, matching the production dry-run):

    pods == 1 :  (stage, data)            e.g. 16 x 16  = one 256-chip pod
    pods >= 2 :  (pod, stage, data)       e.g. 2 x 16 x 16 = two pods

The pod axis is OMITTED from the mesh when ``pods == 1`` so single-pod
programs keep the exact mesh shape (and therefore compiled layout) they had
before the abstraction existed; the schedules receive the data-reduction
axes as a tuple whenever the pod axis is real, which makes gradient
all-reduces span ``("pod", "data")`` — combined data parallelism across
pods, the regime AsyncMesh calls out as the interesting one.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple, Union

from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

STAGE_AXIS = "stage"
DATA_AXIS = "data"
POD_AXIS = "pod"


@dataclass(frozen=True)
class Topology:
    """(pod, stage, data) shape of one SPMD pipeline deployment."""

    stages: int
    data: int = 1
    pods: int = 1

    def __post_init__(self):
        if self.stages < 1 or self.data < 1 or self.pods < 1:
            raise ValueError(f"all topology axes must be >= 1, got {self}")

    # -- shape ---------------------------------------------------------------

    @property
    def shape(self) -> Tuple[int, ...]:
        if self.pods == 1:
            return (self.stages, self.data)
        return (self.pods, self.stages, self.data)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        if self.pods == 1:
            return (STAGE_AXIS, DATA_AXIS)
        return (POD_AXIS, STAGE_AXIS, DATA_AXIS)

    @property
    def num_devices(self) -> int:
        return self.pods * self.stages * self.data

    @property
    def data_shards(self) -> int:
        """Ways the global batch is split: the full (pod, data) extent."""
        return self.pods * self.data

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Mesh axes that carry data parallelism (gradient all-reduce group)."""
        if self.pods == 1:
            return (DATA_AXIS,)
        return (POD_AXIS, DATA_AXIS)

    @property
    def schedule_data_axis(self) -> Union[str, Tuple[str, ...]]:
        """``data_axis`` argument for the tick schedules: the bare axis name
        single-pod (the historical path), the ("pod", "data") tuple multi-pod."""
        if self.pods == 1:
            return DATA_AXIS
        return self.data_axes

    def describe(self) -> str:
        return "x".join(str(s) for s in self.shape)

    def replica_groups(self, axes: Tuple[str, ...]) -> Tuple[Tuple[int, ...], ...]:
        """Replica groups of a collective over ``axes``, as flattened
        positions in the device assignment (row-major over `shape`).

        A reduction over an axis subset partitions the devices by their
        coordinates on the remaining axes — this is the ground truth the
        static HLO auditor (`repro.analysis.hlo`) compares every compiled
        collective against, so only groupings constructible here count as
        "declared by the topology".
        """
        import numpy as np

        unknown = set(axes) - set(self.axis_names)
        if not axes or unknown:
            raise ValueError(
                f"axes {axes} not declared by topology {self.describe()} "
                f"with axes {self.axis_names}"
            )
        names = self.axis_names
        keep = [i for i, n in enumerate(names) if n not in axes]
        move = [i for i, n in enumerate(names) if n in axes]
        ids = np.arange(self.num_devices).reshape(self.shape)
        grouped = ids.transpose(keep + move).reshape(
            -1, int(np.prod([self.shape[i] for i in move]))
        )
        return tuple(tuple(int(x) for x in row) for row in grouped)

    # -- process grid (multi-controller) -------------------------------------

    def local_device_count(self, num_processes: int) -> int:
        """Devices each process contributes: the global grid split into
        equal process-major slabs."""
        if num_processes < 1 or self.num_devices % num_processes != 0:
            raise ValueError(
                f"{self.num_devices} devices of topology {self.describe()} "
                f"do not split over {num_processes} processes"
            )
        return self.num_devices // num_processes

    def _process_coords(self, num_processes: int, process_index: int):
        """(pod, stage, data)-style coordinate rows of one process's slab of
        the row-major global device grid."""
        import numpy as np

        per = self.local_device_count(num_processes)
        if not 0 <= process_index < num_processes:
            raise ValueError(
                f"process_index {process_index} out of range for "
                f"{num_processes} processes"
            )
        flat = np.arange(process_index * per, (process_index + 1) * per)
        return np.stack(np.unravel_index(flat, self.shape), axis=1)

    def process_data_shards(
        self, num_processes: int, process_index: int
    ) -> Tuple[int, int]:
        """Half-open range ``[lo, hi)`` of global data-shard indices (pod-
        major, `data_shards` total) whose batch rows this process must
        supply to `jax.make_array_from_process_local_data`.

        The range is the union of the (pod, data) coordinates of the
        process's device slab — contiguous whenever process boundaries
        don't cut a stage's data extent unevenly (guaranteed when the
        per-process device count and the data extent divide one another,
        the only layouts the launcher produces). Processes that only hold
        stage replicas of the same rows get overlapping ranges — each
        supplies its addressable copy, exactly what the assembly API wants.
        """
        coords = self._process_coords(num_processes, process_index)
        if self.pods == 1:
            rows = coords[:, 1]  # (stage, data) -> data coordinate
        else:
            rows = coords[:, 0] * self.data + coords[:, 2]
        uniq = sorted(set(int(r) for r in rows))
        lo, hi = uniq[0], uniq[-1] + 1
        if uniq != list(range(lo, hi)):
            raise ValueError(
                f"process {process_index}/{num_processes} of topology "
                f"{self.describe()} owns non-contiguous data shards {uniq}; "
                f"choose a process count whose slab size divides (or is a "
                f"multiple of) the data extent"
            )
        return lo, hi

    def shard_owners(self, num_processes: int) -> Tuple[int, ...]:
        """Which process writes checkpoint shard (= pipeline stage) ``s``.

        Candidates are the processes whose device slab touches stage ``s``
        (they address that slice of every stage-sharded leaf); ownership
        round-robins over them so pod-replicated layouts spread the write
        load instead of piling every shard on process 0. Exactly one owner
        per shard — the disjoint-write invariant the multi-process
        checkpointer relies on.
        """
        owners = []
        self.local_device_count(num_processes)  # validate divisibility
        stage_pos = 0 if self.pods == 1 else 1
        by_stage: dict = {}
        for p in range(num_processes):
            for c in self._process_coords(num_processes, p):
                by_stage.setdefault(int(c[stage_pos]), []).append(p)
        for s in range(self.stages):
            cands = sorted(set(by_stage[s]))
            owners.append(cands[s % len(cands)])
        return tuple(owners)

    # -- mesh + specs --------------------------------------------------------

    def make_mesh(self) -> Mesh:
        import jax

        from repro.launch.mesh import auto_axes, make_process_mesh

        if jax.process_count() > 1:
            # multi-controller: the device grid must be row-major so process
            # slabs align with the (pod, stage, data) slabs the data loader
            # and checkpoint shard-ownership maps assume (jax.make_mesh may
            # permute devices for ICI locality)
            return make_process_mesh(self.shape, self.axis_names)
        return jax.make_mesh(self.shape, self.axis_names,
                             axis_types=auto_axes(len(self.axis_names)))

    def stage_spec(self, ndim: int) -> P:
        """Stage-stacked leaf of rank ``ndim``: leading axis over `stage`."""
        return P(STAGE_AXIS, *([None] * (ndim - 1)))

    def batch_spec(self) -> P:
        """(M, mb, S) microbatched tokens: mb sharded over every data axis."""
        return P(None, self.data_axes, None)

    def replicated_spec(self) -> P:
        return P()

    # -- constructors --------------------------------------------------------

    @classmethod
    def single_host(cls, stages: int, data: int = 1) -> "Topology":
        """Test/smoke shape: K forced host devices, optional data axis."""
        return cls(stages=stages, data=data)

    @classmethod
    def single_pod(cls, stages: int = 16, data: int = 16) -> "Topology":
        """The production 16x16 pod (256 chips)."""
        return cls(stages=stages, data=data)

    @classmethod
    def multi_pod(cls, pods: int = 2, stages: int = 16, data: int = 16) -> "Topology":
        """Pod-replicated production shape, e.g. 2x16x16 = 512 chips."""
        return cls(stages=stages, data=data, pods=pods)

    @classmethod
    def from_mesh(cls, mesh: Mesh) -> "Topology":
        """Recover the topology from a mesh built with the canonical axes."""
        dims = dict(zip(mesh.axis_names, mesh.devices.shape))
        unknown = set(dims) - {POD_AXIS, STAGE_AXIS, DATA_AXIS}
        if unknown or STAGE_AXIS not in dims:
            raise ValueError(
                f"mesh axes {mesh.axis_names} are not a pipeline topology "
                f"(expected a subset of (pod, stage, data) containing stage)"
            )
        return cls(stages=dims[STAGE_AXIS], data=dims.get(DATA_AXIS, 1),
                   pods=dims.get(POD_AXIS, 1))

    @classmethod
    def from_device_count(
        cls, stages: int, pods: int = 1, data: int = 0,
        device_count: Optional[int] = None,
    ) -> "Topology":
        """Fill in the data axis from the visible device count.

        ``data == 0`` means "use every device": data = n // (pods * stages).
        On CPU, force host devices first (``--xla_force_host_platform_
        device_count``).
        """
        if device_count is None:
            import jax

            device_count = len(jax.devices())
        if data <= 0:
            if device_count % (pods * stages) != 0:
                raise ValueError(
                    f"{device_count} devices not divisible by pods*stages = "
                    f"{pods}*{stages}"
                )
            data = device_count // (pods * stages)
        return cls(stages=stages, data=data, pods=pods)

    @classmethod
    def from_process_grid(
        cls, stages: int, num_processes: int, local_device_count: int,
        pods: int = 1, data: int = 0,
    ) -> "Topology":
        """Multi-controller constructor: the global grid is the union of
        ``num_processes`` slabs of ``local_device_count`` devices each;
        ``data == 0`` fills the data axis from that total (mirroring
        `from_device_count` for the single-controller path)."""
        return cls.from_device_count(
            stages, pods=pods, data=data,
            device_count=num_processes * local_device_count,
        )
