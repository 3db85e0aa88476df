"""Flash-attention / paged-attention kernel autotuner.

The Pallas flash kernel (ops/flash_attention.py) takes ``block_q``/``block_k``
tile sizes whose best values depend on the chip generation (VMEM size, MXU
shape) and the problem shape.  The reference delegates kernel tuning to
cuDNN/bitsandbytes; on TPU it is OUR kernel, so the framework ships the tuner:
time fwd+bwd over a candidate grid on the attached backend and report the
ranking.

Usage (library)::

    from torchdistpackage_tpu.tools import tune_flash_blocks
    best, report = tune_flash_blocks(batch=8, heads=12, seq=2048, head_dim=64)

or CLI: ``python -m torchdistpackage_tpu.tools.flash_tune --seq 2048``.

``--paged`` tunes the paged decode-attention kernel instead
(ops/paged_attention.py): the candidates are ``fetch_width`` (pool blocks
fetched at a time: the blocks of one KEY TILE where the kernel walks a
slot's live blocks itself, decode and verify; the blocks a grid step
streams where the grid walks the table's columns, a prefill chunk) and
``q_pad_to`` (the q-row padding multiple; the speculative K+1 verify shape
lands at awkward row counts), timed at BOTH decode-program shapes —
``S_in=1`` ordinary decode and ``S_in=K+1`` spec verify — and, where the
shape names a prefill chunk, at its shape too, whose time the report prints
beside (``chunk_rel``).  ``hb`` and ``T`` are the KV heads a program of the
decode shape carries and the key tile the kernel would choose on its own,
which it computes from the shape (``decode_walk``); ``chunk_rows``,
``chunk_tile_keys`` and ``chunk_programs`` say the same of the chunk's call
(``chunk_tile``: a candidate's ``fetch_width`` there is the blocks of the
ONE key tile a grid step makes of what it fetched).  ``--shape
mistral7b.decode``, ``--shape zaya1.reason`` and ``--shape
trinitymini.mixedlen`` (with and without its window) are the benchmark
cells' geometries.  ``_KV_TILE_KEYS``, ``_TILE_SCORE_BYTES``,
``_PROGRAM_ROWS`` and ``_PAGED_PARAMS`` in ops/paged_attention.py are the
consumers of a measured row.

Timing chains the iterations through a data dependency and fetches a scalar
at the end, so the clock stops after the device has finished.
"""

from __future__ import annotations

import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# (block_q, block_k) candidates; clamped per-shape by the kernel's gcd rule
DEFAULT_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (128, 128),
    (128, 256),
    (128, 512),
    (256, 256),
    (256, 512),
    (256, 1024),
    (512, 512),
    (512, 1024),
    (1024, 1024),
)


def _time_config(
    q, k, v, block_q: int, block_k: int, causal: bool, steps: int, warmup: int
) -> float:
    """Seconds per fwd+bwd step for one (block_q, block_k)."""
    from ..ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=causal, block_q=block_q, block_k=block_k
            ).astype(jnp.float32)
        )

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    # chain iterations through q so the run can't dead-code or overlap past
    # the timer; final scalar fetch bounds execution
    def chain(q, n):
        for _ in range(n):
            dq, _, _ = step(q, k, v)
            q = q + 0 * dq
        return q

    q1 = chain(q, warmup)
    float(jnp.sum(q1[0, 0, 0].astype(jnp.float32)))
    t0 = time.perf_counter()
    q2 = chain(q, steps)
    float(jnp.sum(q2[0, 0, 0].astype(jnp.float32)))
    return (time.perf_counter() - t0) / steps


def tune_flash_blocks(
    batch: int = 8,
    heads: int = 12,
    seq: int = 2048,
    head_dim: int = 64,
    causal: bool = True,
    dtype=jnp.bfloat16,
    candidates: Sequence[Tuple[int, int]] = DEFAULT_CANDIDATES,
    steps: int = 10,
    warmup: int = 2,
    seed: int = 0,
) -> Tuple[Tuple[int, int], List[dict]]:
    """Measure every (block_q, block_k) candidate at the given shape.

    Returns ``(best, report)`` where ``report`` is a list of
    ``{"block_q", "block_k", "ms", "rel"}`` sorted fastest-first (``rel`` is
    time relative to the winner).  Candidates that exceed the sequence are
    deduped after the kernel's clamp-to-divisor rule, so the report has no
    repeated effective configs."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (batch, heads, seq, head_dim)
    q = jax.random.normal(kq, shape, dtype)
    k = jax.random.normal(kk, shape, dtype)
    v = jax.random.normal(kv, shape, dtype)

    import math

    seen = set()
    rows = []
    for bq, bk in candidates:
        eff = (math.gcd(min(bq, seq), seq), math.gcd(min(bk, seq), seq))
        if eff in seen:
            continue
        seen.add(eff)
        try:
            dt = _time_config(q, k, v, bq, bk, causal, steps, warmup)
        except Exception as e:  # one bad tile must not kill the sweep
            rows.append({"block_q": eff[0], "block_k": eff[1],
                         "ms": None, "error": repr(e)[:200]})
            continue
        rows.append({"block_q": eff[0], "block_k": eff[1], "ms": dt * 1e3})
    ok = [r for r in rows if r.get("ms") is not None]
    if not ok:
        raise RuntimeError(f"no flash block config succeeded: {rows}")
    ok.sort(key=lambda r: r["ms"])
    best_ms = ok[0]["ms"]
    for r in ok:
        r["rel"] = round(r["ms"] / best_ms, 3)
        r["ms"] = round(r["ms"], 3)
    report = ok + [r for r in rows if r.get("ms") is None]
    return (ok[0]["block_q"], ok[0]["block_k"]), report


# ------------------------------------------------- paged-attention tuner

#: (fetch_width, q_pad_to) candidates for the paged decode kernel;
#: fetch_width is clamped to the table width per shape.  1, 2, 3 and 6
#: divide or cover ``mistral7b.decode``'s six table columns, 4, 5, 10 and
#: 20 ``zaya1.reason``'s twenty, 7 the 21 of ``trinitymini.mixedlen``'s window.
PAGED_CANDIDATES: Tuple[Tuple[Optional[int], int], ...] = (
    (None, 8),  # what the kernel takes from the shape on its own
    (1, 8),
    (2, 8),
    (3, 8),
    (4, 8),
    (5, 8),
    (6, 8),
    (7, 8),
    (8, 8),
    (10, 8),
    (20, 8),
    (4, 16),
    (6, 16),
)

#: Named geometries for ``--shape``: ``tune_paged_params`` arguments.
#: ``mistral7b.decode``: the benchmark cell's engine (64 slots, GQA 32 / 8
#: x 128, block 128, ``max_ctx`` 768, chunk 256 at 8 slots a prefill call,
#: a bf16 pool of 385 blocks), slots 10-70% full (mean ~300 tokens).
#: ``zaya1.reason``: 64 slots, 8 / 2 heads x 128, block 128, ``max_ctx``
#: 2,560 (20 columns), a bf16 pool of 1,281 blocks, contexts 256-2,560.
#: ``trinitymini.mixedlen``: 32 slots, GQA 32 / 4 x 128, block 128,
#: ``max_ctx`` 14,336 (112 columns), chunk 512 at 4 slots a prefill call,
#: contexts 1k-12k; its global layers' walk, and ``-window`` its window
#: layers' (2,048: a chunk reaches over 21 columns wherever it stands).
PAGED_SHAPES = {
    "mistral7b.decode": dict(
        num_slots=64, kv_heads=8, groups=4, head_dim=128, block_size=128,
        max_blocks=6, spec_k=2, chunk=256, chunk_slots=8, fill=(0.1, 0.7),
        dtype="bfloat16"),
    "zaya1.reason": dict(
        num_slots=64, kv_heads=2, groups=4, head_dim=128, block_size=128,
        max_blocks=20, spec_k=2, chunk=256, chunk_slots=8, fill=(0.1, 1.0),
        dtype="bfloat16"),
    "trinitymini.mixedlen": dict(
        num_slots=32, kv_heads=4, groups=8, head_dim=128, block_size=128,
        max_blocks=112, spec_k=0, chunk=512, chunk_slots=4, fill=(0.07, 0.85),
        dtype="bfloat16"),
}
PAGED_SHAPES["trinitymini.mixedlen-window"] = dict(
    PAGED_SHAPES["trinitymini.mixedlen"], window=2048)


def _time_paged_config(
    q_shape, k_pool, v_pool, tables, offsets, fetch_width, q_pad_to,
    steps: int, warmup: int, seed: int, calls: int = 16,
    window: Optional[int] = None,
) -> float:
    """Seconds per call of the kernel at one q shape for one
    (fetch_width, q_pad_to).  One dispatch runs ``calls`` calls chained
    through q (a decode call takes a fraction of a millisecond, less than
    the host needs to launch it)."""
    from ..ops.paged_attention import paged_decode_attention

    q = 0.1 * jax.random.normal(jax.random.PRNGKey(seed), q_shape,
                                k_pool.dtype)

    @jax.jit
    def step(qq, kp, vp):
        def body(qq, _):
            return qq + paged_decode_attention(
                qq, kp, vp, tables, offsets, window=window,
                fetch_width=fetch_width, q_pad_to=q_pad_to), None
        return jax.lax.scan(body, qq, None, length=calls)[0]

    def run(n):
        out = q
        for _ in range(n):
            out = step(q, k_pool, v_pool)
        float(jnp.sum(out[0, 0, 0].astype(jnp.float32)))

    run(max(1, warmup))
    t0 = time.perf_counter()
    run(steps)
    return (time.perf_counter() - t0) / (steps * calls)


def tune_paged_params(
    num_slots: int = 8,
    kv_heads: int = 8,
    groups: int = 2,
    head_dim: int = 64,
    block_size: int = 64,
    max_blocks: int = 64,
    spec_k: int = 2,
    chunk: int = 0,
    chunk_slots: int = 8,
    fill: Tuple[float, float] = (0.25, 1.0),
    dtype="float32",
    window: Optional[int] = None,
    candidates: Sequence[Tuple[int, int]] = PAGED_CANDIDATES,
    steps: int = 10,
    warmup: int = 2,
    seed: int = 0,
) -> Tuple[dict, List[dict]]:
    """Measure every (fetch_width, q_pad_to) candidate at a serving shape:
    a ``[max_blocks*num_slots + 1, kv_heads, block_size, head_dim]`` pool
    with per-slot tables at mixed live lengths (``fill``: the share of the
    max context a slot holds, drawn uniformly between the two), q at
    S_in=1 (decode) AND S_in=spec_k+1 (the verify program); with ``chunk``,
    also ``chunk_slots`` slots' prefill chunk; ``window``: a sliding window
    on every call.  Returns ``(best, report)`` with ``report`` rows
    ``{"fetch_width", "q_pad_to", "hb", "T", "ms", "decode_ms",
    "verify_ms"[, "chunk_ms", "chunk_rel", "chunk_rows", "chunk_tile_keys",
    "chunk_programs"], "rel"}`` sorted fastest-first by ``ms`` = decode +
    verify; ``chunk_rel`` is the chunk's time over the fastest chunk's, and
    the row whose ``fetch_width`` is None is the kernel left to itself."""
    import numpy as np

    from ..ops.paged_attention import shape_walk

    dtype = jnp.dtype(dtype)
    nb = max_blocks * num_slots + 1
    kp = jax.random.normal(
        jax.random.PRNGKey(seed + 1),
        (nb, kv_heads, block_size, head_dim), dtype)
    vp = jax.random.normal(
        jax.random.PRNGKey(seed + 2),
        (nb, kv_heads, block_size, head_dim), dtype)
    rng = np.random.RandomState(seed)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, nb))[:num_slots * max_blocks]
        .reshape(num_slots, max_blocks), jnp.int32)
    max_ctx = max_blocks * block_size
    offsets = jnp.asarray(
        rng.randint(int(max_ctx * fill[0]),
                    min(int(max_ctx * fill[1]), max_ctx - spec_k - 1),
                    size=num_slots), jnp.int32)
    H = kv_heads * groups
    # shape name -> (slots, S_in, offsets)
    shapes = {"decode": (num_slots, 1, offsets),
              "verify": (num_slots, spec_k + 1, offsets)}
    if chunk:
        shapes["chunk"] = (
            chunk_slots, chunk,
            jnp.minimum(offsets[:chunk_slots] // chunk * chunk,
                        max_ctx - chunk))

    rows = []
    block_bytes = block_size * head_dim * dtype.itemsize
    for fw, pad in candidates:
        if fw is not None and fw > max_blocks:
            continue
        *_, hb, T = shape_walk(
            groups, 1, kv_heads, max_blocks, block_size, block_bytes, window,
            q_pad_to=pad)
        row = {"fetch_width": fw, "q_pad_to": pad, "hb": hb, "T": T}
        if chunk:  # the chunk's call as the wrapper asks for it
            split, _cols, c_rows, c_fw, c_hb, c_T = shape_walk(
                groups, chunk, kv_heads, max_blocks, block_size, block_bytes,
                window, fetch_width=fw, q_pad_to=pad)
            row.update(chunk_rows=c_rows,
                       chunk_tile_keys=(c_T or c_fw) * block_size,
                       chunk_programs=kv_heads * split // c_hb)
        try:
            for name, (slots, s_in, offs) in shapes.items():
                row[f"{name}_ms"] = 1e3 * _time_paged_config(
                    (slots, H, s_in, head_dim), kp, vp, tables[:slots],
                    offs, fw, pad, steps, warmup, seed, window=window)
            row["ms"] = row["decode_ms"] + row["verify_ms"]
        except Exception as e:  # one bad config must not kill the sweep
            row.update(ms=None, error=repr(e)[:200])
        rows.append(row)
    ok = [r for r in rows if r.get("ms") is not None]
    if not ok:
        raise RuntimeError(f"no paged config succeeded: {rows}")
    ok.sort(key=lambda r: r["ms"])
    best_ms = ok[0]["ms"]
    best_chunk = min(r.get("chunk_ms", 0.0) for r in ok)
    for r in ok:
        r["rel"] = round(r["ms"] / best_ms, 3)
        if chunk:
            r["chunk_rel"] = round(r["chunk_ms"] / best_chunk, 3)
        for k in [k for k in r if k == "ms" or k.endswith("_ms")]:
            r[k] = round(r[k], 3)
    report = ok + [r for r in rows if r.get("ms") is None]
    named = [r for r in ok if r["fetch_width"] is not None]
    best = {"fetch_width": named[0]["fetch_width"],
            "q_pad_to": named[0]["q_pad_to"]}
    return best, report


def main(argv: Optional[Sequence[str]] = None) -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--no-causal", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="tune the paged decode-attention kernel "
                         "(fetch_width x q_pad_to at the serving shapes) "
                         "instead of the flash training kernel")
    ap.add_argument("--slots", type=int, default=8,
                    help="--paged: decode-batch width")
    ap.add_argument("--kv-heads", type=int, default=8,
                    help="--paged: KV heads (q heads = groups * kv_heads)")
    ap.add_argument("--block-size", type=int, default=64,
                    help="--paged: pool block size")
    ap.add_argument("--max-blocks", type=int, default=64,
                    help="--paged: table width (max_ctx / block_size)")
    ap.add_argument("--spec-k", type=int, default=2,
                    help="--paged: verify draft width (S_in = K+1 shape)")
    ap.add_argument("--shape", choices=sorted(PAGED_SHAPES),
                    help="--paged: a named geometry (with its prefill chunk "
                         "and pool dtype), in place of the options above")
    args = ap.parse_args(argv)
    from ..utils.logging import master_print

    if args.paged:
        shape = dict(PAGED_SHAPES[args.shape]) if args.shape else dict(
            num_slots=args.slots, kv_heads=args.kv_heads,
            head_dim=args.head_dim, block_size=args.block_size,
            max_blocks=args.max_blocks, spec_k=args.spec_k)
        best, report = tune_paged_params(steps=args.steps, **shape)
        master_print(json.dumps({
            "kernel": "paged_attention",
            "backend": jax.default_backend(),
            "chip": jax.devices()[0].device_kind,
            "shape": shape,
            "best": best,
            "report": report,
        }, indent=1))
        return
    best, report = tune_flash_blocks(
        batch=args.batch, heads=args.heads, seq=args.seq,
        head_dim=args.head_dim, causal=not args.no_causal, steps=args.steps,
    )
    master_print(json.dumps({
        "backend": jax.default_backend(),
        "chip": jax.devices()[0].device_kind,
        "shape": [args.batch, args.heads, args.seq, args.head_dim],
        "best": {"block_q": best[0], "block_k": best[1]},
        "report": report,
    }, indent=1))


if __name__ == "__main__":
    main()
