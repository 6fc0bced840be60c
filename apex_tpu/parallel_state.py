"""Mesh and axis registry — the TPU-native model-parallel state.

Capability parity with ``apex/transformer/parallel_state.py`` ::
``initialize_model_parallel``, ``get_tensor_model_parallel_group/_rank/
_world_size``, ``get_pipeline_model_parallel_*``, ``get_data_parallel_*``,
``is_pipeline_first_stage`` / ``is_pipeline_last_stage``,
``set_virtual_pipeline_model_parallel_rank``, ``destroy_model_parallel``.

The reference builds ~10 ``torch.distributed`` process groups over NCCL for a
3D (DP x PP x TP) rank grid.  On TPU there are no process groups: the single
SPMD program runs over a named :class:`jax.sharding.Mesh` and "groups" are
mesh axes.  A collective over the tensor-parallel "group" is simply
``jax.lax.psum(x, axis_name="tp")`` inside :func:`jax.shard_map`.

Axis layout
-----------
The canonical mesh is ``(dp, pp, tp)`` with ``tp`` innermost (fastest
varying) so that tensor-parallel collectives — the highest-bandwidth traffic,
fired twice per transformer layer per direction (see SURVEY.md §3.4) — map to
physically adjacent chips over ICI, while ``dp`` (lowest frequency, gradient
all-reduce once per step) may span DCN on multi-slice topologies.  Megatron
sequence parallelism ("sp") reuses the ``tp`` axis by construction (the SP
all-gather / reduce-scatter pair replaces the TP identity/all-reduce pair over
the *same* ranks), exactly like the reference where SP collectives run on the
TP process group.

Rank queries
------------
In SPMD there is no host-side "my rank": every host traces one program for
all devices.  Rank helpers (:func:`get_tensor_model_parallel_rank` etc.)
return a *traced* index via ``jax.lax.axis_index`` and are therefore valid
only inside ``shard_map`` (or any context binding the axis name).  World-size
helpers are static Python ints valid anywhere.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = [
    "DATA_PARALLEL_AXIS",
    "PIPELINE_PARALLEL_AXIS",
    "CONTEXT_PARALLEL_AXIS",
    "TENSOR_PARALLEL_AXIS",
    "EXPERT_PARALLEL_AXIS",
    "get_expert_model_parallel_world_size",
    "get_expert_model_parallel_rank",
    "initialize_model_parallel",
    "model_parallel_is_initialized",
    "get_mesh",
    "get_data_parallel_world_size",
    "get_context_parallel_world_size",
    "get_context_parallel_rank",
    "get_tensor_model_parallel_world_size",
    "get_pipeline_model_parallel_world_size",
    "get_data_parallel_rank",
    "get_tensor_model_parallel_rank",
    "get_pipeline_model_parallel_rank",
    "get_tensor_model_parallel_src_rank",
    "get_pipeline_model_parallel_next_rank",
    "get_pipeline_model_parallel_prev_rank",
    "is_pipeline_first_stage",
    "is_pipeline_last_stage",
    "get_virtual_pipeline_model_parallel_rank",
    "set_virtual_pipeline_model_parallel_rank",
    "get_virtual_pipeline_model_parallel_world_size",
    "set_virtual_pipeline_model_parallel_world_size",
    "destroy_model_parallel",
    "register_sequence_parallel_param",
    "sequence_parallel_param_prefix",
    "sequence_parallel_param_paths",
    "clear_sequence_parallel_params",
    "divide",
    "bound_axis_size",
    "axis_is_bound",
    "data_parallel_sharding",
    "named_sharding",
    "replicated_sharding",
]

DATA_PARALLEL_AXIS = "dp"
PIPELINE_PARALLEL_AXIS = "pp"
CONTEXT_PARALLEL_AXIS = "cp"
TENSOR_PARALLEL_AXIS = "tp"
# Expert parallelism rides the dp axis (Megatron's convention: the expert
# group is carved from the data-parallel world; no extra mesh axis) — see
# apex_tpu.transformer.moe.  The alias names the intent at call sites.
EXPERT_PARALLEL_AXIS = DATA_PARALLEL_AXIS

_AXIS_ORDER = (
    DATA_PARALLEL_AXIS,
    PIPELINE_PARALLEL_AXIS,
    CONTEXT_PARALLEL_AXIS,
    TENSOR_PARALLEL_AXIS,
)


@dataclasses.dataclass
class _ParallelState:
    mesh: Mesh
    data_parallel_size: int
    pipeline_model_parallel_size: int
    tensor_model_parallel_size: int
    context_parallel_size: int = 1
    virtual_pipeline_model_parallel_size: Optional[int] = None
    # Virtual-pipeline rank is plain host state mutated by the interleaved
    # 1F1B scheduler, mirroring the reference's module-global
    # (parallel_state.py :: set_virtual_pipeline_model_parallel_rank).
    virtual_pipeline_model_parallel_rank: Optional[int] = None
    # SP partial-grad param marks live ON the state object so that
    # destroy/initialize cycles (and thus different models) can never
    # share marks (advisor r2: process-global registry cross-contamination).
    sequence_parallel_param_paths: set = dataclasses.field(
        default_factory=set
    )


_STATE: Optional[_ParallelState] = None


def _ici_device_mesh(dp, pp, cp, tp, devices):
    """Topology-aware single-granule layout: on a real TPU slice a naive
    reshape of jax.devices() can place a tp group across non-adjacent
    chips; mesh_utils computes an ICI-friendly layout (innermost axis on
    the tightest torus dimension)."""
    import numpy as np
    from jax.experimental import mesh_utils

    try:
        return mesh_utils.create_device_mesh((dp, pp, cp, tp), devices=devices)
    except Exception as e:
        import warnings

        warnings.warn(
            f"mesh_utils.create_device_mesh failed ({type(e).__name__}: {e});"
            " falling back to naive device ordering — tp groups may span"
            " non-adjacent chips, degrading collective bandwidth",
            RuntimeWarning,
            stacklevel=2,
        )
        return np.asarray(devices).reshape(dp, pp, cp, tp)


def initialize_model_parallel(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    virtual_pipeline_model_parallel_size: Optional[int] = None,
    context_parallel_size: int = 1,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    dcn_data_parallel: bool = False,
) -> Mesh:
    """Create and register the global ``(dp, pp, tp)`` mesh.

    ≙ ``apex/transformer/parallel_state.py :: initialize_model_parallel``.
    Where the reference carves ``world_size`` ranks into NCCL groups, this
    reshapes ``jax.devices()`` into a named mesh.  ``dp`` is derived:
    ``n_devices // (tp * pp)``, with the same divisibility requirement the
    reference enforces.

    ``dcn_data_parallel=True`` is the multi-slice layout (≙ the
    reference's convention of putting the DP all-reduce on the
    inter-node fabric and TP inside NVLink islands): the mesh is built
    with ``mesh_utils.create_hybrid_device_mesh`` so that one dp
    sub-axis of size ``jax.process_count()``-granularity spans DCN while
    pp/cp/tp (and the rest of dp) stay on ICI.  Gradient psum over
    ``dp`` then does a hierarchical reduce: ICI first, one DCN hop
    last.  Ignored (with a warning) when the topology gives a single
    slice or the hybrid construction is unavailable.

    Returns the mesh (also retrievable via :func:`get_mesh`).
    """
    global _STATE
    if _STATE is not None:
        # ≙ the reference's "group is already initialized" asserts.
        raise RuntimeError(
            "model parallel state is already initialized — call "
            "destroy_model_parallel() first"
        )
    explicit_devices = devices is not None
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    tp = int(tensor_model_parallel_size)
    pp = int(pipeline_model_parallel_size)
    cp = int(context_parallel_size)
    if tp < 1 or pp < 1 or cp < 1:
        raise ValueError("parallel sizes must be >= 1")
    if n % (tp * pp * cp) != 0:
        raise RuntimeError(
            f"world size ({n}) is not divisible by tensor_model_parallel_size "
            f"({tp}) x pipeline_model_parallel_size ({pp}) x "
            f"context_parallel_size ({cp})"
        )
    dp = n // (tp * pp * cp)
    if virtual_pipeline_model_parallel_size is not None:
        if pp < 2:
            raise RuntimeError(
                "pipeline-model-parallel size should be greater than 1 with "
                "interleaved schedule"
            )
    import numpy as np

    if explicit_devices:
        device_array = np.asarray(devices).reshape(dp, pp, cp, tp)
    elif dcn_data_parallel:
        # Multi-slice: split dp into (dcn_granules, dp_within) and ask
        # mesh_utils for a hybrid mesh — model axes never cross DCN.
        from jax.experimental import mesh_utils

        granules = len({d.process_index for d in devices})
        try:
            if granules == 1 or dp % granules != 0:
                raise ValueError(
                    f"dp={dp} not splittable over {granules} DCN granule(s)"
                )
            # process_is_granule matches the process_index-based granule
            # count above (jax's default groups by slice_index, which CPU
            # devices lack and which disagrees with this count on
            # multi-host-per-slice pods)
            device_array = mesh_utils.create_hybrid_device_mesh(
                (dp // granules, pp, cp, tp),
                (granules, 1, 1, 1),
                devices=devices,
                process_is_granule=True,
            )
        except Exception as e:
            import warnings

            warnings.warn(
                f"hybrid (DCN) mesh unavailable ({type(e).__name__}: {e}); "
                "using the single-granule ICI layout",
                RuntimeWarning,
                stacklevel=2,
            )
            device_array = _ici_device_mesh(dp, pp, cp, tp, devices)
    else:
        device_array = _ici_device_mesh(dp, pp, cp, tp, devices)
    mesh = Mesh(device_array, _AXIS_ORDER)
    _STATE = _ParallelState(
        mesh=mesh,
        data_parallel_size=dp,
        pipeline_model_parallel_size=pp,
        tensor_model_parallel_size=tp,
        context_parallel_size=cp,
        virtual_pipeline_model_parallel_size=virtual_pipeline_model_parallel_size,
        virtual_pipeline_model_parallel_rank=(
            0 if virtual_pipeline_model_parallel_size is not None else None
        ),
    )
    # Fresh mesh epoch ⇒ fresh SP registry: drop any meshless-era marks so
    # they cannot bleed into this mesh's models.
    _SEQUENCE_PARALLEL_PARAM_PATHS.clear()
    return mesh


def model_parallel_is_initialized() -> bool:
    """≙ parallel_state.py :: model_parallel_is_initialized."""
    return _STATE is not None


def _state() -> _ParallelState:
    if _STATE is None:
        raise RuntimeError(
            "model parallel state is not initialized — call "
            "apex_tpu.parallel_state.initialize_model_parallel() first"
        )
    return _STATE


def get_mesh() -> Mesh:
    """The registered global mesh (axes ``dp``, ``pp``, ``cp``, ``tp``)."""
    return _state().mesh


# ---------------------------------------------------------------------------
# World sizes — static host ints.
# ---------------------------------------------------------------------------


def get_data_parallel_world_size() -> int:
    return _state().data_parallel_size


def get_tensor_model_parallel_world_size() -> int:
    return _state().tensor_model_parallel_size


def get_context_parallel_world_size() -> int:
    """Size of the ``cp`` axis (ring/context parallelism; 1 = disabled).

    No reference analog: the reference has no context parallelism
    (SURVEY §2.3 capability envelope) — this is the TPU-native extension
    for long-context scaling over the ICI torus."""
    return _state().context_parallel_size


def get_pipeline_model_parallel_world_size() -> int:
    return _state().pipeline_model_parallel_size


# ---------------------------------------------------------------------------
# Ranks — traced values, valid inside shard_map over the global mesh.
# ---------------------------------------------------------------------------


def axis_is_bound(axis: str) -> bool:
    """Whether ``axis`` is a bound mesh axis here (inside shard_map) —
    regardless of its size (a bound size-1 axis is still bound)."""
    try:
        jax.lax.axis_size(axis)
        return True
    except (NameError, KeyError):
        return False


def bound_axis_size(axis: str) -> int:
    """Size of ``axis`` if bound (inside shard_map over the mesh), else 1.

    The shared probe for modules that degrade gracefully outside a mesh
    (SyncBatchNorm, groupbn, SwitchMoe): jax raises NameError/KeyError for
    an unbound name depending on the path, both meaning "no such axis
    here".
    """
    try:
        return jax.lax.axis_size(axis)
    except (NameError, KeyError):
        return 1


def _axis_index(axis: str):
    try:
        return jax.lax.axis_index(axis)
    except NameError as e:  # axis name not bound: not inside shard_map
        raise RuntimeError(
            f"rank query for axis {axis!r} is only meaningful inside "
            "jax.shard_map over the global mesh (SPMD has no host-side rank); "
            "use the *_world_size helpers for host logic"
        ) from e


def get_expert_model_parallel_world_size() -> int:
    """Experts shard over the dp axis; its size is the ep world size.
    (≙ Megatron's get_expert_model_parallel_world_size — absent in the
    reference fork, provided here for the MoE extension.)"""
    return _state().data_parallel_size


def get_expert_model_parallel_rank():
    """Traced ep rank (== dp rank) — call inside shard_map."""
    return _axis_index(EXPERT_PARALLEL_AXIS)


def get_data_parallel_rank():
    return _axis_index(DATA_PARALLEL_AXIS)


def get_tensor_model_parallel_rank():
    return _axis_index(TENSOR_PARALLEL_AXIS)


def get_pipeline_model_parallel_rank():
    return _axis_index(PIPELINE_PARALLEL_AXIS)


def get_context_parallel_rank():
    return _axis_index(CONTEXT_PARALLEL_AXIS)


def get_tensor_model_parallel_src_rank():
    """Rank 0 of the tensor-parallel group.

    ≙ parallel_state.py :: get_tensor_model_parallel_src_rank.  In mesh terms
    the "source" is simply index 0 along ``tp``; data broadcast from it is a
    no-op under SPMD (all members trace identical programs), so this exists
    for API parity and for `tensor_parallel.data.broadcast_data`.
    """
    return 0


def get_pipeline_model_parallel_next_rank():
    pp = get_pipeline_model_parallel_world_size()
    return (get_pipeline_model_parallel_rank() + 1) % pp


def get_pipeline_model_parallel_prev_rank():
    pp = get_pipeline_model_parallel_world_size()
    return (get_pipeline_model_parallel_rank() - 1) % pp


def is_pipeline_first_stage(ignore_virtual: bool = False):
    """Traced boolean (inside shard_map); honors virtual pipeline rank.

    ≙ parallel_state.py :: is_pipeline_first_stage.
    """
    if not ignore_virtual:
        vpp = get_virtual_pipeline_model_parallel_world_size()
        if vpp is not None and get_virtual_pipeline_model_parallel_rank() != 0:
            return False
    return get_pipeline_model_parallel_rank() == 0


def is_pipeline_last_stage(ignore_virtual: bool = False):
    if not ignore_virtual:
        vpp = get_virtual_pipeline_model_parallel_world_size()
        if vpp is not None and (
            get_virtual_pipeline_model_parallel_rank() != vpp - 1
        ):
            return False
    pp = get_pipeline_model_parallel_world_size()
    return get_pipeline_model_parallel_rank() == pp - 1


# ---------------------------------------------------------------------------
# Virtual pipeline (interleaved 1F1B) bookkeeping — host state.
# ---------------------------------------------------------------------------


def get_virtual_pipeline_model_parallel_rank() -> Optional[int]:
    return _state().virtual_pipeline_model_parallel_rank


def set_virtual_pipeline_model_parallel_rank(rank: int) -> None:
    _state().virtual_pipeline_model_parallel_rank = rank


def get_virtual_pipeline_model_parallel_world_size() -> Optional[int]:
    return _state().virtual_pipeline_model_parallel_size


def set_virtual_pipeline_model_parallel_world_size(size: Optional[int]) -> None:
    st = _state()
    st.virtual_pipeline_model_parallel_size = size
    if size is None:
        st.virtual_pipeline_model_parallel_rank = None
    elif st.virtual_pipeline_model_parallel_rank is None:
        # Keep the first/last-stage predicates well-defined when virtual PP
        # is enabled after init (rank defaults to chunk 0, as in __init__).
        st.virtual_pipeline_model_parallel_rank = 0


def destroy_model_parallel() -> None:
    """≙ parallel_state.py :: destroy_model_parallel."""
    global _STATE
    _STATE = None
    clear_sequence_parallel_params()


# ---------------------------------------------------------------------------
# Sequence-parallel partial-gradient param registry.
#
# ≙ Megatron's ``param.sequence_parallel = True`` attribute marking: under
# Megatron-style SP, params used inside the sequence-sharded region (layer
# norms, RowParallelLinear biases, MoE router/experts, position embeddings)
# are REPLICATED across tp but each rank computes their gradient from only
# its S/tp sequence shard — the true gradient is the SUM over tp ranks.
# Torch marks the parameter object; params here are plain arrays, so
# modules register the param's tree path at trace time instead, and
# ``allreduce_sequence_parallel_gradients`` (tensor_parallel.mappings)
# psums exactly the registered paths.
#
# Scoping: marks are stored on the live ``_ParallelState`` when a mesh is
# initialized — destroy/initialize cycles start with a clean registry, so
# two models traced across cycles can never cross-contaminate.  The
# module-level set only backs the meshless case (tp=1 unit tests) and is
# cleared on both destroy AND initialize.
# ---------------------------------------------------------------------------

_SEQUENCE_PARALLEL_PARAM_PATHS: set = set()


def _sp_registry() -> set:
    if _STATE is not None:
        return _STATE.sequence_parallel_param_paths
    return _SEQUENCE_PARALLEL_PARAM_PATHS


_SEQUENCE_PARALLEL_PATH_PREFIX: tuple = ()


@contextlib.contextmanager
def sequence_parallel_param_prefix(prefix):
    """Paths registered inside start with ``prefix``: for a module applied
    on its own (``Module.apply``) inside another, whose ``self.path`` starts
    at itself and not where its parameters sit in the caller's tree."""
    global _SEQUENCE_PARALLEL_PATH_PREFIX
    outer = _SEQUENCE_PARALLEL_PATH_PREFIX
    _SEQUENCE_PARALLEL_PATH_PREFIX = outer + tuple(str(p) for p in prefix)
    try:
        yield
    finally:
        _SEQUENCE_PARALLEL_PATH_PREFIX = outer


def register_sequence_parallel_param(path) -> None:
    """Mark the param at ``path`` (module path + param name, a tuple of
    strings, excluding the "params" collection key) as having tp-partial
    gradients under sequence parallelism."""
    _sp_registry().add(
        _SEQUENCE_PARALLEL_PATH_PREFIX + tuple(str(p) for p in path)
    )


def sequence_parallel_param_paths() -> frozenset:
    return frozenset(_sp_registry())


def clear_sequence_parallel_params() -> None:
    _sp_registry().clear()


# ---------------------------------------------------------------------------
# Sharding helpers (no reference analog — mesh idioms the rest of the
# framework builds on).
# ---------------------------------------------------------------------------


def named_sharding(*spec) -> NamedSharding:
    """NamedSharding over the global mesh for a PartitionSpec."""
    return NamedSharding(get_mesh(), P(*spec))


def data_parallel_sharding(ndim: int) -> NamedSharding:
    """Batch-leading sharding: dim 0 split over ``dp``, rest replicated."""
    spec = [DATA_PARALLEL_AXIS] + [None] * (ndim - 1)
    return NamedSharding(get_mesh(), P(*spec))


def replicated_sharding() -> NamedSharding:
    return NamedSharding(get_mesh(), P())


def divide(numerator: int, denominator: int) -> int:
    """≙ apex/transformer/utils.py :: divide (ensure_divisibility + floordiv)."""
    if numerator % denominator != 0:
        raise ValueError(f"{numerator} is not divisible by {denominator}")
    return numerator // denominator
