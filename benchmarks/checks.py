"""The comparisons that decide ``correct``: what the timed path produced,
against the plain reference, each number beside its limit.  The limits are
data in the cell's file; how each was set is in PERF.md."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmarks.arch import Arch


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Any]:
    """Every number compared needs a limit of its own and must be within it."""
    rows, ok = [], True
    for name, value in numbers.items():
        limit = limits[name]
        good = bool(np.isfinite(value) and value <= limit)
        ok &= good
        rows.append({"number": name, "value": float(value), "limit": limit,
                     "within": good})
    return {"correct": ok, "compared": rows}


# ---------------------------------------------------------------- training


def train_numbers(program: Dict[str, Any], reference) -> Dict[str, float]:
    """``program``: what the timed step object gave on its first steps
    (``losses``, ``grad_norms`` after step one read from the optimizer's
    first moment, ``update_norms`` of the parameters' change after the last
    checked step).  ``reference``: a ``TrainReference`` that followed the
    same steps, plus ``update_norms``."""
    from benchmarks.reference.train import without_gradient, worst_gap

    n = len(reference.losses)
    loss_gap = max(abs(p - r) for p, r in zip(program["losses"][:n],
                                              reference.losses))
    g = worst_gap(program["grad_norms"], reference.grad_norms[0])
    # a leaf with no gradient (the key bias) is held by grad_norm_gap alone
    dead = without_gradient(reference.grad_norms[0])
    u = worst_gap(program["update_norms"], reference.update_norms, skip=dead)
    return {"loss_gap": loss_gap, "grad_norm_gap": g["gap"],
            "update_norm_gap": u["gap"],
            "_leaves": {"grad_norm_gap": g["leaf"], "update_norm_gap": u["leaf"],
                        "without_gradient": dead}}


def follow_training(a: Arch, seed: int, opt: Dict[str, float],
                    batches: Sequence[Dict[str, np.ndarray]],
                    quant: Optional[str] = None):
    """The reference through the same steps from the same seeded weights."""
    import jax

    from benchmarks.reference import train as T
    from benchmarks.weights import make_weights

    ref = T.TrainReference(make_weights(a, seed), a, opt, quant=quant)
    for b in batches:
        ref.step(b["tokens"], b["targets"])
    ref.mu = ref.nu = None
    p0 = make_weights(a, seed)
    ref.update_norms = {k: float(v) for k, v in jax.device_get(
        T.delta_norms(ref.params, p0)).items()}
    del p0
    ref.params = None
    return ref


# ----------------------------------------------------------------- serving


def served_gap(params, a: Arch, sample: List[Dict[str, Any]],
               quant: Optional[str] = None, pad_to: int = 0) -> Dict[str, Any]:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``sample`` (a run gives
    every request that its window finished).  With
    ``quant`` the program's tokens are not read at all: the token that the
    lower precision puts first at each position stands in for them.
    Sequences are padded at the end to ``pad_to`` (causal: earlier positions
    do not see the padding), so that the reference is one set of programs."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference.model import forward_logits

    @jax.jit
    def gaps_of(ref, served):
        """Per position: the reference's best logit minus the served one's."""
        return jnp.max(ref, axis=-1) - jnp.take_along_axis(
            ref, served[:, None], axis=-1)[:, 0]

    worst, total, n_tokens, n_off = 0.0, 0.0, 0, 0
    for req in sample:
        toks = np.asarray(req["tokens"], np.int32)
        p, n = int(req["prompt_len"]), len(toks)
        size = max(pad_to, n - 1)
        inp = np.zeros(size, np.int32)
        inp[:n - 1] = toks[:-1]
        ref = forward_logits(params, inp, a)       # row t predicts token t+1
        if quant is None:
            served = np.zeros(size, np.int32)
            served[:n - 1] = toks[1:]
            served = jnp.asarray(served)
        else:
            served = jnp.argmax(forward_logits(params, inp, a, quant),
                                axis=-1).astype(jnp.int32)
        # every shape above is the padded one: one set of programs, cached;
        # the served positions are cut out on the host
        gaps = np.asarray(gaps_of(ref, served))[p - 1:n - 1]
        worst = max(worst, float(gaps.max()))
        total += float(gaps.sum())
        n_tokens += len(gaps)
        n_off += int((gaps > 0).sum())
    return {"served_logit_gap": worst, "tokens": n_tokens, "not_top": n_off,
            "mean_gap": total / max(1, n_tokens)}
