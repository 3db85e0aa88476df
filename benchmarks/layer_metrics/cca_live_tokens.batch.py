from benchmarks import stats
from benchmarks.layer_metrics import program_spans


def read(obs):
    """Live cached positions a decode call attends to, median over the
    window's calls; None where the span carries no such attr."""
    win = program_spans.window(obs)
    live = [k[5]["live_tokens"] for kids in win[1] for k in kids
            if k[2] == "tdp:engine.decode" and "live_tokens" in k[5]
            ] if win else []
    return stats.median(live) if live else None
