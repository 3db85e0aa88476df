from benchmarks.layer_metrics import mimo_kernels as K


def read(obs):
    """``decode_bytes_roofline.batch`` for this cell: EVERY operation inside
    the executions of the program that runs ``swa_decode`` against the whole
    step as the traced decode spans and the family's unit costs count it
    (``mimo_kernels.call_costs``: every weight but the routed experts'
    once, the held experts that call touched, both kinds' K and V)."""
    return K.roofline(obs, K.DECODE_KERNEL, "^%", "step", "one")
