from benchmarks.layer_metrics import swa_kernels as K


def read(obs):
    """The window layers' decode kernel against ONE window layer's call as
    the traced decode spans count it (the positions inside the window
    alone), ``window_layers`` calls an execution of the program that runs
    it.  None where the spans or the family give nothing."""
    return K.roofline(obs, K.DECODE_KERNEL, K.DECODE_KERNEL, "window",
                      "window_layers")
