"""Collective bandwidth benchmark — analogue of the reference's
``torchdistpackage/dist/py_comm_test.py`` (84 LoC).

The reference times NCCL all_reduce / all_gather / reduce_scatter /
all_to_all and reports algorithm- and bus-bandwidth with the nccl-tests
correction factors (py_comm_test.py:10-17,49-51).  Here the same harness runs
jitted XLA collectives over any named mesh axis, so the numbers measure
ICI/DCN (or the CPU-sim fabric in tests).  Bus-bandwidth factors follow the
same convention:

- all_reduce:      busbw = algbw * 2 * (n-1)/n
- all_gather:      busbw = algbw * (n-1)/n
- reduce_scatter:  busbw = algbw * (n-1)/n
- all_to_all:      busbw = algbw * (n-1)/n
- ppermute (ring p2p): busbw = algbw (each link carries the payload once)

algbw = bytes / time, where bytes is the *full* (global) payload size, as in
nccl-tests.

Results are **obs-schema comm records** (``obs.comm_ledger.comm_record``:
op / axis / bytes / time_s / algbw_GBps / busbw_GBps) — the same shape the
HLO ledger aggregates and the alpha-beta model calibrates against
(``obs.comm_model.CommModel.calibrate``), so measurement, calibration, and
reporting round-trip through one schema.  ``test_collection`` can stream
them to any obs sink (``JsonlSink`` et al.) instead of ad-hoc dicts.

Int8-ring arms (PR 8): ``int8_all_reduce`` / ``int8_reduce_scatter`` /
``int8_all_gather`` time the quantized rings of ``dist/compressed.py``
through the same harness.  Their records keep ``bytes`` at the ORIGINAL
payload (directly comparable to the exact arm's row; effective busbw
above the link rate IS the compression win) and add ``compressed`` /
``base_op`` / ``elem_bytes`` — the fields
``CommModel.calibrate(compressed_ops=...)`` uses to refit alpha/beta
against the compressed wire bytes, grounding
``predict_compressed`` in measurement (quant FLOPs included).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..obs.comm_ledger import comm_record
from .topology import tpc

_BUSBW_FACTOR = {
    "all_reduce": lambda n: 2 * (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
    # int8-ring arms (dist/compressed.py): busbw uses the base op's factor
    # over the ORIGINAL payload — an EFFECTIVE bus bandwidth directly
    # comparable to the exact arm's row (the wire moves ~4x fewer bytes,
    # so effective busbw above the link rate is the compression win;
    # CommModel.calibrate refits against the compressed wire bytes).
    "int8_all_reduce": lambda n: 2 * (n - 1) / n,
    "int8_reduce_scatter": lambda n: (n - 1) / n,
    "int8_all_gather": lambda n: (n - 1) / n,
}


def _timeit(fn, arg, warmup: int = 2, iters: int = 10) -> float:
    """Median wall time of ``fn(arg)`` with device sync, seconds."""
    for _ in range(warmup):
        jax.block_until_ready(fn(arg))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(arg))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_collective(
    op: str,
    axis: str,
    nbytes: int = 1 << 24,
    mesh: Optional[Mesh] = None,
    dtype=jnp.bfloat16,
    warmup: int = 2,
    iters: int = 10,
) -> Dict[str, float]:
    """Time one collective over ``axis`` and return timing + bandwidth stats.

    ``nbytes`` is the global payload size (like the reference's tensor size,
    py_comm_test.py:22-30).  Returns an obs-schema comm record
    (``{op, axis, axis_size, bytes, time_s, algbw_GBps, busbw_GBps}``).
    """
    if mesh is None:
        mesh = tpc.get_view()
    n = mesh.shape[axis]
    elem = jnp.dtype(dtype).itemsize
    # divisible by n (and by n*n for all_to_all's [count//n, n] local split)
    quantum = n * n if op == "all_to_all" else n
    count = max(quantum, nbytes // elem // quantum * quantum)

    if op == "all_reduce":
        body = lambda x: jax.lax.psum(x, axis)
        in_spec, out_spec = P(), P()
        shape = (count,)
    elif op == "all_gather":
        body = lambda x: jax.lax.all_gather(x, axis, tiled=True)
        in_spec, out_spec = P(axis), P(axis)
        shape = (count,)
    elif op == "reduce_scatter":
        body = lambda x: jax.lax.psum_scatter(x, axis, tiled=True)
        in_spec, out_spec = P(), P(axis)
        shape = (count,)
    elif op == "all_to_all":
        body = lambda x: jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=0, tiled=True)
        in_spec, out_spec = P(axis), P(axis)
        shape = (count // n, n)
    elif op == "ppermute":
        perm = [(i, (i + 1) % n) for i in range(n)]
        body = lambda x: jax.lax.ppermute(x, axis, perm)
        in_spec, out_spec = P(axis), P(axis)
        shape = (count,)
    # --- int8-ring arms (dist/compressed.py): same harness, quantized
    # wire.  bytes on the record stays the ORIGINAL payload (nccl-tests
    # convention, comparable to the exact arm); calibration derives the
    # compressed wire bytes from it (obs.comm_model.compressed_wire_bytes
    # via the record's elem_bytes).
    elif op == "int8_all_reduce":
        from .compressed import int8_ring_pmean

        body = lambda x: int8_ring_pmean(x, axis) * n  # sum, mirrors psum
        in_spec, out_spec = P(), P()
        shape = (count,)
    elif op == "int8_reduce_scatter":
        from .compressed import int8_ring_reduce_scatter

        body = lambda x: int8_ring_reduce_scatter(x, axis, 0)
        in_spec, out_spec = P(), P(axis)
        shape = (count,)
    elif op == "int8_all_gather":
        from .compressed import int8_ring_all_gather

        body = lambda x: int8_ring_all_gather(x, axis, 0)
        in_spec, out_spec = P(axis), P(axis)
        shape = (count,)
    else:
        raise ValueError(f"unknown collective {op!r}")

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec))
    x = jnp.ones(shape, dtype=dtype)
    t = _timeit(fn, x, warmup=warmup, iters=iters)
    size = x.size * elem
    algbw = size / t / 1e9
    extra = (
        {"compressed": True, "base_op": op[len("int8_"):], "elem_bytes": elem}
        if op.startswith("int8_") else {}
    )
    return comm_record(
        op=op,
        axis=axis,
        nbytes=size,
        axis_size=n,
        time_s=t,
        algbw_GBps=algbw,
        busbw_GBps=algbw * _BUSBW_FACTOR[op](n),
        **extra,
    )


def test_collection(
    axis: str,
    sizes: Sequence[int] = (1 << 20, 1 << 24),
    ops: Sequence[str] = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all", "ppermute"),
    mesh: Optional[Mesh] = None,
    verbose: bool = True,
    sink: Optional[Any] = None,
) -> List[Dict[str, float]]:
    """Sweep collectives x sizes over an axis — analogue of
    ``test_collection`` (py_comm_test.py:20-57).

    ``sink``: an obs sink (anything with ``write(record)``) or a path
    string — each comm record is streamed there as JSONL on the master
    process, the package's one structured-output path (no ad-hoc dicts).
    """
    if isinstance(sink, str):
        from ..obs.exporters import JsonlSink

        sink = JsonlSink(sink)
    rows = []
    is_master = True
    try:
        is_master = jax.process_index() == 0
    except Exception:
        pass
    for op in ops:
        for nbytes in sizes:
            row = bench_collective(op, axis, nbytes=nbytes, mesh=mesh)
            rows.append(row)
            if sink is not None and is_master:
                try:
                    sink.write(row)
                except Exception:
                    pass
            if verbose:
                from ..utils.logging import master_print

                master_print(
                    f"{op:>14} axis={axis}({row['axis_size']}) "
                    f"{row['bytes']/2**20:8.1f} MiB  "
                    f"{row['time_s']*1e3:8.3f} ms  "
                    f"alg {row['algbw_GBps']:7.2f} GB/s  "
                    f"bus {row['busbw_GBps']:7.2f} GB/s"
                )
    return rows
