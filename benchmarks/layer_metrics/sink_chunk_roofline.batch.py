from benchmarks.layer_metrics import mimo_kernels as K


def read(obs):
    """The window layers' chunk kernel (``swa_chunk``) against ONE window
    layer's call as the traced prefill spans count it (``window_pairs`` at
    ``window_unit``), ``window_layers`` calls an execution of the compact
    prefill program."""
    return K.roofline(obs, K.CHUNK_KERNEL, K.CHUNK_KERNEL, "window",
                      "window_layers")
