from benchmarks.layer_metrics import swa_kernels as K


def read(obs):
    """``paged_decode_roofline.batch`` for a cell whose most frequent
    program is not its decode call: the GLOBAL layers' ``paged_decode``
    inside the executions of the program that runs ``swa_decode``, against
    every live position's K and V as the traced decode spans count them
    (``live_tokens``), one call a ``*`` layer of the shape.  None where no
    program runs ``swa_decode`` (another model, a parent commit)."""
    return K.roofline(obs, K.DECODE_KERNEL, "^%paged_decode[.0-9]* ",
                      "global", "calls_per_execution")
