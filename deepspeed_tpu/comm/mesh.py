"""Device-mesh topology for all parallelism axes.

TPU-native replacement for the reference's process-group bookkeeping
(``deepspeed/utils/groups.py`` — DP/TP/EP/SP/PP group creation — and
``deepspeed/comm/comm.py:609 initialize_mesh_device``).  Instead of creating
torch.distributed subgroups per parallelism flavor, we build ONE
``jax.sharding.Mesh`` whose named axes carry every degree; XLA's GSPMD
partitioner then derives each "group" from the axis names used in shardings
and collectives.

Axis naming convention (outer → inner, chosen so the innermost axes map to
ICI-adjacent devices on real TPU slices):

    pipe   — pipeline-parallel stages        (ref: runtime/pipe/topology.py)
    data   — pure data parallel              (ref: groups._get_data_parallel_group)
    expert — expert parallel, subdivides DP  (ref: groups._create_expert_and_data_parallel)
    seq    — Ulysses sequence parallel       (ref: groups._create_sequence_parallel_group)
    tensor — tensor/model parallel           (ref: groups._get_model_parallel_group)

ZeRO partitions over (data, expert, seq) — the combined data-parallel world,
matching the reference's use of ``seq_data_parallel_group`` for ZeRO
(ref: runtime/engine.py:1677) and expert-data groups for MoE params.
"""

import contextlib
import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.logging import logger

PIPE_AXIS = "pipe"
DATA_AXIS = "data"
EXPERT_AXIS = "expert"
SEQ_AXIS = "seq"
TENSOR_AXIS = "tensor"
MESH_AXES = (PIPE_AXIS, DATA_AXIS, EXPERT_AXIS, SEQ_AXIS, TENSOR_AXIS)

# Axes over which ZeRO shards params/grads/optimizer state.
ZERO_AXES = (DATA_AXIS, EXPERT_AXIS, SEQ_AXIS)
# Axes over which a data batch is split.
BATCH_AXES = (DATA_AXIS, EXPERT_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    pipe: int = 1
    data: int = -1  # -1: absorb remaining devices
    expert: int = 1
    seq: int = 1
    tensor: int = 1

    def resolve(self, n_devices: int) -> Tuple[int, int, int, int, int]:
        fixed = self.pipe * self.expert * self.seq * self.tensor
        data = self.data
        if data == -1:
            if n_devices % fixed != 0:
                raise ValueError(f"{n_devices} devices not divisible by pipe*expert*seq*tensor={fixed}")
            data = n_devices // fixed
        if self.pipe * data * self.expert * self.seq * self.tensor != n_devices:
            raise ValueError(
                f"Mesh {self} does not cover {n_devices} devices "
                f"(pipe={self.pipe} data={data} expert={self.expert} seq={self.seq} tensor={self.tensor})")
        return (self.pipe, data, self.expert, self.seq, self.tensor)


def create_mesh(spec: Optional[MeshSpec] = None,
                devices: Optional[Sequence] = None,
                axis_names: Sequence[str] = MESH_AXES) -> Mesh:
    """Build the global device mesh.

    The device order from ``jax.devices()`` follows physical torus order on
    TPU, so contiguous inner axes land on ICI neighbours — collectives for
    tensor/seq/expert ride ICI while pipe/data may span DCN, matching the
    bandwidth hierarchy the reference manages manually via NCCL subgroups.
    """
    spec = spec or MeshSpec()
    devices = list(devices if devices is not None else jax.devices())
    shape = spec.resolve(len(devices))
    dev_array = np.asarray(devices).reshape(shape)
    mesh = Mesh(dev_array, axis_names=tuple(axis_names))
    logger.debug(f"Created mesh {dict(zip(axis_names, shape))} over {len(devices)} devices")
    return mesh


_GLOBAL_MESH: Optional[Mesh] = None


def set_global_mesh(mesh: Mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh() -> Mesh:
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        _GLOBAL_MESH = create_mesh()
    return _GLOBAL_MESH


def has_global_mesh() -> bool:
    return _GLOBAL_MESH is not None


_TRACE_MESH: Optional[Mesh] = None


def _mpu_degree(mpu, names, default=1) -> int:
    """First present-and-callable accessor wins (Megatron renamed these
    across versions: get_model_parallel_world_size →
    get_tensor_model_parallel_world_size)."""
    for n in names:
        fn = getattr(mpu, n, None)
        if callable(fn):
            return int(fn())
    return default


def mesh_from_mpu(mpu) -> Mesh:
    """Map an external Megatron-style mpu grid onto the named mesh.

    ref: the reference engine consumes ``mpu.get_{model,data}_parallel_*``
    to build its NCCL groups (deepspeed/runtime/engine.py _configure_
    distributed_model; utils/groups.py honors an external mpu everywhere).
    Here the same degrees select mesh-axis sizes — TP → 'tensor',
    PP → 'pipe', DP → 'data' — and GSPMD derives every group from the axis
    names, so AutoTP rules, ZeRO partitioning and collectives all follow
    the external grid without translating its process groups."""
    tp = _mpu_degree(mpu, ("get_tensor_model_parallel_world_size",
                           "get_model_parallel_world_size"))
    pp = _mpu_degree(mpu, ("get_pipeline_model_parallel_world_size",
                           "get_pipe_parallel_world_size"))
    dp = _mpu_degree(mpu, ("get_data_parallel_world_size", ), default=-1)
    need = tp * pp * (dp if dp > 0 else 1)
    n = len(jax.devices())
    if need > n:
        raise ValueError(f"mpu grid tp={tp} pp={pp} dp={dp} needs {need} devices, "
                         f"have {n}")
    if dp <= 0:
        dp = n // (tp * pp)
    mesh = create_mesh(MeshSpec(pipe=pp, data=dp, tensor=tp),
                       devices=jax.devices()[:tp * pp * dp])
    logger.info(f"mesh_from_mpu: tp={tp} pp={pp} dp={dp}")
    return mesh


@contextlib.contextmanager
def trace_mesh(mesh: Optional[Mesh]):
    """Context manager marking *which mesh governs the computation being
    traced*.  Engines wrap their jitted-fn invocations (where tracing
    happens) in this; kernels that must wrap themselves in shard_map under a
    multi-device mesh (Mosaic custom calls cannot be auto-partitioned by
    GSPMD) consult it via ``get_trace_mesh``.  Deliberately NOT the global
    mesh: that is process-wide and would hijack unrelated jits — e.g. a
    single-device eval traced after an 8-device training engine was built."""
    global _TRACE_MESH
    prev = _TRACE_MESH
    _TRACE_MESH = mesh
    try:
        yield
    finally:
        _TRACE_MESH = prev


def get_trace_mesh() -> Optional[Mesh]:
    return _TRACE_MESH


def traced_for_tpu() -> bool:
    """Whether the computation being traced is for a TPU: asked of the
    governing mesh, not of the local devices, so that a compile for an
    offline TPU topology from a CPU-only host lowers the real kernels and not
    their interpreted form.  Every Pallas kernel's ``interpret=None`` is this
    rule."""
    dev = _TRACE_MESH.devices.flat[0] if _TRACE_MESH is not None else jax.devices()[0]
    return dev.platform == "tpu"


def in_manual_mesh() -> bool:
    """True inside a shard_map body: GSPMD-level sharding constraints are
    meaningless/illegal there, and shard_map-wrapping kernels must not
    re-wrap."""
    from jax.sharding import get_abstract_mesh
    return bool(get_abstract_mesh().manual_axes)


def axis_size(mesh: Mesh, *axes: str) -> int:
    return int(np.prod([mesh.shape[a] for a in axes if a in mesh.shape]))


def dp_world_size(mesh: Optional[Mesh] = None) -> int:
    """Combined data-parallel degree (the ZeRO partition count)."""
    mesh = mesh or get_global_mesh()
    return axis_size(mesh, *ZERO_AXES)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a [batch, ...] input: batch split over DP axes, seq over SP."""
    return NamedSharding(mesh, P(BATCH_AXES, SEQ_AXIS if mesh.shape.get(SEQ_AXIS, 1) > 1 else None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
