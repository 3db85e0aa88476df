from benchmarks.layer_metrics import dsa_kernels


def read(obs):
    """``decode_bytes_roofline.batch`` for a cell whose most frequent program
    is not its decode call: EVERY operation inside the executions of the
    program that runs ``dsa_decode`` (dsa_kernels.py says why) against the
    family's ``decode_step`` cost.  None where the family gives none."""
    one = obs["costs"].get("decode_step")
    return dsa_kernels.roofline(obs, "^%", one,
                                (one or {}).get("calls_per_execution", 0))
