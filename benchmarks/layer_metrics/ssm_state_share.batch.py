from benchmarks.layer_metrics import ssm_step


def read(obs):
    """The recurrent state's part of a decode call's least bytes, median
    over the window's decode calls, percent; None where the spans or the
    family give nothing."""
    return ssm_step.state_share(obs)
