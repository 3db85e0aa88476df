from benchmarks.layer_metrics import mimo_kernels as K


def read(obs):
    """The window layers' decode kernel (``swa_decode``: 8 KV heads, keys of
    192 over values of 128, a sink a query head) against ONE window layer's
    call as the traced decode spans count it (the positions inside the
    window alone, at ``window_unit``), ``window_layers`` calls an execution
    of the program that runs it."""
    return K.roofline(obs, K.DECODE_KERNEL, K.DECODE_KERNEL, "window",
                      "window_layers")
