from benchmarks.layer_metrics import program_spans


def read(obs):
    """The pool's bytes, the indexer's leaf included, as the program's own
    ``tdp:engine.init.pool`` span says them, GB; None where the span says it
    holds no such leaf (a parent commit, another model)."""
    win = program_spans.window(obs)
    pools = [r for r in win[2] if r[2] == "tdp:engine.init.pool"
             and "index_bytes" in r[5]] if win else []
    return pools[-1][5]["bytes"] * 1e-9 if pools else None
