from benchmarks.layer_metrics import ssm_step


def read(obs):
    """``device_bytes`` over ``bytes`` of the pool's and the state's init
    spans together; None where either span carries no ``device_bytes`` (a
    parent commit, a backend that counts no memory)."""
    return ssm_step.padding_ratio(obs)
