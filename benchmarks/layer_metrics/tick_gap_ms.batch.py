from benchmarks.layer_metrics import program_spans


def read(obs):
    return program_spans.tick_gap_ms(obs)
