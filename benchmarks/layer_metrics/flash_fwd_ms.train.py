from benchmarks.layer_metrics import program_spans


def read(obs, **args):
    return program_spans.kernel_ms(obs, **args)
