from benchmarks.layer_metrics import program_spans


def read(obs):
    """The window layers' pool as the program's own ``tdp:engine.init.pool``
    span says it (``window_bytes``), GB; None where the span says it holds
    no such pool (a parent commit, another model)."""
    win = program_spans.window(obs)
    pools = [r for r in win[2] if r[2] == "tdp:engine.init.pool"
             and "window_bytes" in r[5]] if win else []
    return pools[-1][5]["window_bytes"] * 1e-9 if pools else None
