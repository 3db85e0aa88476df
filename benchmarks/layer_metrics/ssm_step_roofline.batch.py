from benchmarks.layer_metrics import ssm_step


def read(obs):
    """EVERY operation inside the executions of the program that runs
    ``paged_decode`` against the whole decode step, each traced execution
    at ITS OWN call's ``slots`` and ``live_tokens``; None where the spans,
    the trace or the family give nothing."""
    return ssm_step.step_roofline(obs)
