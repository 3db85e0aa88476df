from benchmarks.layer_metrics import dsa_kernels


def read(obs):
    """``readers.roofline`` with cost ``paged_decode``, over the executions
    of the program that runs the kernel (dsa_kernels.py says why)."""
    one = obs["costs"].get("paged_decode")
    return dsa_kernels.roofline(obs, dsa_kernels.DECODE_KERNEL, one,
                                (one or {}).get("calls_per_execution", 0))
