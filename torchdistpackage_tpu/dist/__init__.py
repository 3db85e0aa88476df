from .topology import (
    CONTEXT_AXIS,
    DATA_AXIS,
    EXPERT_AXIS,
    MOE_DATA_AXIS,
    PIPE_AXIS,
    TENSOR_AXIS,
    ParallelContext,
    check_placement,
    is_using_pp,
    test_comm,
    tpc,
)
from .launch import setup_distributed, find_free_port
from . import autoplan
from . import comm_bench
from . import overlap
