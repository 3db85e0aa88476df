from benchmarks.layer_metrics import idle_by_phase


def read(obs):
    return idle_by_phase.idle_ms(obs, "deliver")
