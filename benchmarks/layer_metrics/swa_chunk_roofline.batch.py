from benchmarks.layer_metrics import swa_kernels as K


def read(obs):
    """The window layers' prefill kernel inside the executions of the
    program that runs it, against ONE window layer's prefill call as the
    traced prefill spans count it.  None where the spans or the family give
    nothing."""
    return K.roofline(obs, K.CHUNK_KERNEL, K.CHUNK_KERNEL, "window",
                      "window_layers")
