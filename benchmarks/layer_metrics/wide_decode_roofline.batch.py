from benchmarks.layer_metrics import mimo_kernels as K


def read(obs):
    """The GLOBAL layers' decode kernel (``paged_decode``: 4 KV heads, 16
    query rows a head, keys of 192 over values of 128, no sink) inside the
    executions of the program that runs ``swa_decode``, against every live
    position's K and V as the traced decode spans count them
    (``live_tokens`` at ``global_unit``), one call a ``*`` layer."""
    return K.roofline(obs, K.DECODE_KERNEL, K.WIDE_DECODE, "global",
                      "calls_per_execution")
