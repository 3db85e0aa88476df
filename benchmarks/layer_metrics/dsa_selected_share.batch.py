from benchmarks.layer_metrics import program_spans


def read(obs):
    """Selected over indexed (query, position) pairs of the window's calls,
    percent; None where the spans carry no such attrs (a parent commit, a
    model whose attention is not indexed)."""
    win = program_spans.window(obs)
    calls = [k[5] for kids in win[1] for k in kids
             if k[2] in program_spans.DISPATCH and "indexed_positions" in k[5]
             ] if win else []
    indexed = sum(c["indexed_positions"] for c in calls)
    return (100.0 * sum(c["selected_positions"] for c in calls) / indexed
            if indexed else None)
