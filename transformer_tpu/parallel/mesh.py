"""Device-mesh construction and multi-host initialization.

Replaces the reference's replica topology (an explicit ``'/device:GPU:i'``
list handed to MirroredStrategy, ``distributed_train.py:137-138``) with a
logical 6-axis mesh:

    ('data', 'fsdp', 'model', 'seq', 'pipe', 'expert')

- gradients psum over 'data'+'fsdp'+'expert' (ICI),
- parameters/optimizer shard over 'fsdp',
- attention heads / dff shard over 'model',
- sequence blocks shard over 'seq' (ring attention),
- layer-stack stages over 'pipe' (GPipe schedule; activations hop
  stage-to-stage via ppermute — ``parallel/pipeline.py``),
- MoE expert weights over 'expert' (token slots reach their experts via the
  GSPMD-inserted all-to-all — ``ops/moe.py``).

TPU pods are multi-process by construction — ``initialize_distributed`` wraps
``jax.distributed.initialize`` so the same entry point works single-host (no-op)
and on a pod slice; the reference has no multi-host story at all (SURVEY §2.4).
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

from transformer_tpu.config import MeshConfig


def make_mesh(cfg: MeshConfig, devices: list | None = None) -> Mesh:
    """Build the logical mesh over the given (default: all) devices.

    Axis order puts 'data' slowest and 'seq'/'model' fastest so that the
    axes with the heaviest collectives (TP all-reduces, ring permutes) land on
    nearest-neighbour ICI links when the physical topology allows.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    want = cfg.num_devices
    if want != len(devices):
        raise ValueError(
            f"mesh {cfg.axis_sizes} needs {want} devices, have {len(devices)} "
            f"({[str(d) for d in devices[:4]]}...). Enforced like the "
            "reference's batch/replica divisibility check "
            "(distributed_train.py:154-158)."
        )
    if cfg.dcn_data > 1:
        return _hybrid_mesh(cfg, devices)
    if devices and devices[0].platform == "tpu":
        # Topology-aware placement: on real TPU slices the physical ICI
        # graph is a torus, and a naive row-major reshape can put a
        # heavy-collective axis (model all-reduce, seq/pipe ring) across
        # non-adjacent chips. mesh_utils maps logical axes onto physical
        # torus axes (deterministic for a given topology, so every host in
        # a pod computes the same assignment). CPU/GPU fall through to the
        # plain reshape — there is no torus to exploit.
        try:
            from jax.experimental import mesh_utils

            arr = mesh_utils.create_device_mesh(
                cfg.axis_sizes, devices=devices, allow_split_physical_axes=True
            )
            return Mesh(arr, cfg.axis_names)
        except Exception as e:  # unusual topology: the reshape below is valid
            import warnings

            warnings.warn(
                "topology-aware mesh placement unavailable "
                f"({type(e).__name__}: {e}); falling back to row-major "
                "device order — heavy-collective axes may land on "
                "non-adjacent chips",
                RuntimeWarning,
                stacklevel=2,
            )
    arr = np.asarray(devices).reshape(cfg.axis_sizes)
    return Mesh(arr, cfg.axis_names)


def _hybrid_mesh(cfg: MeshConfig, devices: list) -> Mesh:
    """Multi-slice mesh: the data axis spans ``cfg.dcn_data`` DCN-connected
    granules (TPU slices, or processes off-TPU), every other axis stays
    inside one granule. Slow DCN hops then carry only the data-parallel
    gradient all-reduce; fsdp gathers, tensor-parallel all-reduces, and the
    seq/pipe rings all ride intra-slice ICI (the reference's single-host
    NCCL topology has no counterpart — SURVEY §2.4 multi-host).
    """
    from jax.experimental import mesh_utils

    if cfg.data % cfg.dcn_data:
        raise ValueError(
            f"dcn_data={cfg.dcn_data} must divide the data axis ({cfg.data}): "
            "the data axis is the only one spanning DCN"
        )
    per_slice = (cfg.data // cfg.dcn_data, *cfg.axis_sizes[1:])
    dcn = (cfg.dcn_data, 1, 1, 1, 1, 1)
    # Granule choice: TPU multi-slice runs distinguish devices by
    # slice_index; everywhere else (CPU/GPU fleets — and single-slice
    # backends, where slice_index exists but is 0 on every device) the
    # process is the DCN granule. Decide by whichever attribute actually
    # distinguishes more than one granule.
    slice_vals = {getattr(d, "slice_index", None) for d in devices}
    try:
        arr = mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn, devices=devices,
            process_is_granule=len(slice_vals) <= 1,
            allow_split_physical_axes=True,  # parity with the flat TPU path
        )
    except ValueError as e:
        hint = (
            " Hint: dcn_data must equal the number of DCN granules (TPU "
            "slices, or processes off-TPU) the devices span."
            if "granule" in str(e) or "slices" in str(e).lower()
            else ""
        )
        raise ValueError(
            f"hybrid mesh {per_slice} x dcn {dcn} failed: {e}.{hint}"
        ) from e
    return Mesh(arr, cfg.axis_names)


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Multi-host bring-up. On TPU pods the runtime provides everything and a
    bare ``jax.distributed.initialize()`` suffices; explicit args support
    CPU/GPU fleets.

    Must run before any JAX call that initializes the XLA backend (including
    ``jax.process_count()``/``jax.devices()``) — ``jax.distributed.initialize``
    raises otherwise, so this function probes initialization state without
    touching the backend and re-raises real bring-up failures instead of
    silently degrading to a single-host run."""
    if jax.distributed.is_initialized():
        return  # already initialized (e.g. by the launcher)
    if coordinator_address is None and num_processes is None and process_id is None:
        # Auto-detection: only meaningful where a cluster environment exists
        # (TPU pod metadata, SLURM, ...). Absent one, stay single-process.
        try:
            jax.distributed.initialize()
        except (RuntimeError, ValueError, OSError):
            # No cluster environment to auto-detect (missing coordinator
            # address / unreachable peers): stay single-process.
            return
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
