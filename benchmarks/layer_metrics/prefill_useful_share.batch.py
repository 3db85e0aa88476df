from benchmarks.layer_metrics import program_spans


def read(obs):
    return program_spans.prefill_useful_share(obs)
