from benchmarks.layer_metrics import dsa_kernels


def read(obs):
    """The indexer's cost lies one key deeper than ``readers.roofline``
    looks: the family's ``paged_decode`` returns its ``{flops, bytes}`` under
    ``indexer`` (the runner hands over ``costs['paged_decode']`` and
    ``['decode_step']`` alone).  None where the family gives none."""
    one = obs["costs"].get("paged_decode") or {}
    return dsa_kernels.roofline(obs, "^%dsa_index[.0-9]* ", one.get("indexer"),
                                one.get("calls_per_execution", 0))
