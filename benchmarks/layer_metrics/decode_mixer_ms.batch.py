from benchmarks.layer_metrics import scope_ms


def read(obs, **args):
    return scope_ms.read(obs, **args)
