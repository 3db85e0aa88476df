"""Utility layer — determinism, partitioning, logging and profiling ranges.

Analogue of the reference's ``utils.py`` (fix_rand + partition_params) and
``torchdistpackage/dist/utils.py`` (NVTX ranges, nsys capture gating,
inf/nan probe, master-only print).
"""

from .metrics import MetricsLogger
from .data import (
    global_batch_from_local,
    microbatch,
    prefetch_to_sharding,
    shard_batch,
)
from .random import fix_rand, axis_unique_key, per_axis_keys
from .partition import partition_params
from .logging import (
    disable_non_master_print,
    enable_all_print,
    is_master,
    master_only,
    master_print,
)
from .profiling import (
    note_program,
    op_scopes,
    prof_start,
    prof_stop,
    scope_decorator,
    span,
    spans,
)
from .checkpoint import (
    CheckpointManager,
    auto_resume,
    get_mp_ckpt_suffix,
    load_checkpoint,
    save_checkpoint,
)
from .preemption import GracefulShutdown

__all__ = [
    "MetricsLogger",
    "global_batch_from_local",
    "microbatch",
    "prefetch_to_sharding",
    "shard_batch",
    "fix_rand",
    "axis_unique_key",
    "per_axis_keys",
    "partition_params",
    "disable_non_master_print",
    "enable_all_print",
    "is_master",
    "master_only",
    "master_print",
    "note_program",
    "op_scopes",
    "prof_start",
    "prof_stop",
    "scope_decorator",
    "span",
    "spans",
    "CheckpointManager",
    "GracefulShutdown",
    "auto_resume",
    "get_mp_ckpt_suffix",
    "load_checkpoint",
    "save_checkpoint",
]
