from benchmarks.layer_metrics import program_spans


def read(obs):
    """Positions the window layers hold for the window's calls' slots over
    those the other layers hold (``window_positions`` over ``live_tokens``
    of the dispatch spans, summed), percent: what the window pool keeps of
    what one pool would; None where the spans carry no such attrs (a parent
    commit, a model with one pool)."""
    win = program_spans.window(obs)
    calls = [k[5] for kids in win[1] for k in kids
             if k[2] in program_spans.DISPATCH and "window_positions" in k[5]
             ] if win else []
    live = sum(c["live_tokens"] for c in calls)
    return (100.0 * sum(c["window_positions"] for c in calls) / live
            if live else None)
