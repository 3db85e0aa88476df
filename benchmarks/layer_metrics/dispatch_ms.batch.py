from benchmarks.layer_metrics import program_spans


def read(obs):
    return program_spans.dispatch_ms(obs)
