from benchmarks.layer_metrics import mimo_kernels as K


def read(obs):
    """``device_bytes`` over ``bytes`` of the pool's init span alone, both
    pools: 1.0 where nothing is padded; a 192-wide key leaf held at 256
    lanes would read ~1.2."""
    return K.padding_ratio(obs)
