from benchmarks.layer_metrics import mimo_kernels as K


def read(obs):
    """The GLOBAL layers' chunk kernel (``paged_chunk``: 8,192 rows a KV
    head in two programs over key tiles of 1,024) inside the executions of
    the program that runs ``swa_chunk``, against the (row, key) pairs the
    traced prefill spans count (``live_pairs`` at ``global_unit``), one call
    a ``*`` layer: where a head of 192 meets the MXU at contexts to 24k."""
    return K.roofline(obs, K.CHUNK_KERNEL, K.WIDE_CHUNK, "global",
                      "calls_per_execution")
