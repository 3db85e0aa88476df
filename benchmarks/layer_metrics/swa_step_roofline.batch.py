from benchmarks.layer_metrics import swa_kernels as K


def read(obs):
    """``decode_bytes_roofline.batch`` for a cell whose most frequent program
    is not its decode call: EVERY operation inside the executions of the
    program that runs ``swa_decode`` against the whole step as the traced
    decode spans and the family's unit costs count it
    (``swa_kernels.call_costs``).  None where either gives nothing."""
    return K.roofline(obs, K.DECODE_KERNEL, "^%", "step", "one")
