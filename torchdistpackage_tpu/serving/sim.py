"""Device-step seam: the engine's compiled dispatch behind one interface.

``ServingEngine`` owns exactly three device programs — the shared
prefill/decode step, the speculative verify step, and the admission-path
copy-on-write — plus two tiny device touches (pool allocation and the
per-admission PRNG key).  Everything else in the engine is host-side
scheduling.  This module factors those five touches behind a
:class:`DeviceStep` so the SAME engine (same queue, same admission gate,
same preemption/shed/deadline policy, same allocator and audit) can run
against either backend:

- :class:`CompiledDeviceStep` — the real thing.  Delegates to the
  engine's existing ``_build_step`` / ``_build_verify_step`` /
  ``_build_cow`` and :func:`~.paged_cache.init_paged_kv`, including the
  mesh/shard_map path.  Constructed by default; an engine built without
  a ``device_step=`` argument is bit-for-bit the engine before this seam
  existed.
- :class:`StubDeviceStep` — a host-only double.  No jax
  dispatch, no compilation, no model params (pass ``params=None``): the
  pool is a tiny int8 pytree with the real block layout (dim 1 = blocks,
  ``shape[3] = block_size``, so ``pool_bytes`` / ``block_size_of`` and
  the router's lane-vector migration all work on it), tokens come from a
  deterministic hash, and a :class:`LatencyModel` accumulates what each
  dispatch WOULD have cost so replays report simulated device time next
  to host wall time.  This is what lets ``tools/trace_replay.py`` push
  10^5+ requests through the real Router + real engines on CPU in
  seconds, and what the compile-free policy tests run on.

The stub's token function is chosen so the engine's PARITY claims keep
meaning on it: a greedy row's token depends only on ``(last_token,
position)`` — both restored by a drain descriptor or a cross-replica
``export_slot``/``import_slot`` handoff — and a sampled row additionally
folds in the slot's key stream, which descriptors carry verbatim.  A
request migrated mid-flight therefore continues bit-identically on the
stub exactly as it does on the compiled pair, so routing-policy tests
ported onto the stub still assert real invariants, not stub accidents.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import numpy as np

#: Multiplier/mix constants for the stub's deterministic token hash —
#: arbitrary odd constants (Knuth/Fibonacci hashing); the only contract
#: is determinism and full-range mixing.
_MIX_A = np.uint64(2654435761)
_MIX_B = np.uint64(0x9E3779B97F4A7C15)
_LCG_MUL = np.uint64(6364136223846793005)
_LCG_ADD = np.uint64(1442695040888963407)


class DeviceStep:
    """Interface between ``ServingEngine`` and its device programs.

    ``bind(engine)`` is called once from the engine constructor, after
    the engine's shape attributes (``num_slots``/``block_size``/
    ``num_blocks``/``dp``/``mesh``…) are set but before any program is
    built; the implementation reads what it needs off the engine.

    Attributes
    ----------
    host_only: True when the implementation never touches a device —
        the engine refuses to combine such a step with a mesh, and the
        Router routes its block migrations through
        :func:`host_migrate_blocks` instead of a compiled copy.
    wrap_steps: False opts out of ``telemetry.wrap_step`` AOT
        instrumentation (which would ``jax.jit`` a host callable).
    """

    host_only = False
    wrap_steps = True

    def bind(self, engine: Any) -> None:
        self.engine = engine

    def init_cache(self) -> Any:
        raise NotImplementedError

    def init_state(self) -> Any:
        """A state model's recurrent state, one row a slot (engines of
        every other model never ask)."""
        raise NotImplementedError(
            f"{type(self).__name__} keeps no recurrent state: a state "
            f"model runs on the compiled step only")

    def step_fn(self) -> Callable:
        """``(params, cache, tokens[B,S], tables, offsets, last_idx,
        samp, keys) -> (cache, tok[B], keys)`` — the shared
        prefill-chunk / decode step."""
        raise NotImplementedError

    def verify_fn(self) -> Callable:
        """``(params, cache, tokens[B,K+1], tables, offsets, samp, keys)
        -> (cache, ver[B,K+1], acc[B,K], keys)`` — speculative verify."""
        raise NotImplementedError

    def cow_fn(self) -> Callable:
        """``(cache, src[B], dst[B]) -> cache`` — admission-path COW."""
        raise NotImplementedError

    def prng_key(self, seed: int) -> np.ndarray:
        """Per-request key state, ``uint32[2]`` (threefry layout)."""
        raise NotImplementedError


class CompiledDeviceStep(DeviceStep):
    """The real compiled pair — exactly the engine's pre-seam behavior,
    including the mesh device_put of the pool and shard_map'd programs."""

    def init_cache(self) -> Any:
        import jax

        from .paged_cache import init_paged_kv

        eng = self.engine
        cache = init_paged_kv(eng.cfg, eng.dp * eng.num_blocks,
                              eng.block_size, quantized=eng.kv_quant,
                              window_blocks=eng.window_blocks)
        if eng.mesh is not None:
            from jax.sharding import NamedSharding

            cache = jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(eng.mesh, s)),
                cache, eng._cache_specs(cache))
        return cache

    def init_state(self) -> Any:
        from ..models.hybrid import init_state

        return init_state(self.engine.cfg, self.engine.num_slots)

    def step_fn(self) -> Callable:
        return self.engine._build_step()

    def verify_fn(self) -> Callable:
        return self.engine._build_verify_step()

    def cow_fn(self) -> Callable:
        return self.engine._build_cow()

    def prng_key(self, seed: int) -> np.ndarray:
        import jax

        # on the host's own device where there is one: a key is two words,
        # and on the accelerator it queues behind a call in flight, so an
        # admission would wait for the device (engine ``run_ahead``)
        try:
            host = jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            return np.asarray(jax.random.PRNGKey(seed), np.uint32)
        with jax.default_device(host):
            return np.asarray(jax.random.PRNGKey(seed), np.uint32)


class LatencyModel:
    """Predicted seconds per stub dispatch — the 'calibrated' half of
    the replay stub.  An affine model per program:
    ``base_s + per_token_s * (rows * width)``, the shape a decode tick's
    time has at serving batch sizes (dispatch overhead + linear token
    work).  Fit the coefficients from a real engine's tick medians (a
    traced ``benchmarks/run.py`` cell) when absolute numbers matter; the
    defaults are CPU-sim magnitudes, good for RELATIVE policy curves
    (which routing knob moved goodput), not for absolute TTFT claims."""

    def __init__(
        self,
        prefill_base_s: float = 4e-4,
        prefill_per_token_s: float = 6e-6,
        decode_base_s: float = 3e-4,
        decode_per_token_s: float = 2e-5,
        verify_base_s: float = 4e-4,
        verify_per_token_s: float = 8e-6,
        cow_s: float = 1e-4,
    ) -> None:
        self.coeffs = {
            "prefill": (prefill_base_s, prefill_per_token_s),
            "decode": (decode_base_s, decode_per_token_s),
            "verify": (verify_base_s, verify_per_token_s),
            "cow": (cow_s, 0.0),
        }

    def step_s(self, kind: str, rows: int, width: int = 1) -> float:
        base, per_tok = self.coeffs[kind]
        return base + per_tok * rows * width


class StubDeviceStep(DeviceStep):
    """Host-only :class:`DeviceStep`: numpy pool, hash tokens, modeled
    latency.  ``calls``/``sim_s`` accumulate per-program dispatch counts
    and modeled device seconds (``sim_summary()`` snapshots both) —
    what trace_replay reports as the simulated-device side of a run."""

    host_only = True
    wrap_steps = False

    def __init__(self, latency: Optional[LatencyModel] = None) -> None:
        self.latency = latency if latency is not None else LatencyModel()
        self.calls: Dict[str, int] = {
            "prefill": 0, "decode": 0, "verify": 0, "cow": 0}
        self.sim_s = 0.0

    def _charge(self, kind: str, rows: int, width: int = 1) -> None:
        self.calls[kind] += 1
        self.sim_s += self.latency.step_s(kind, rows, width)

    def sim_summary(self) -> Dict[str, Any]:
        return {"sim_device_s": round(self.sim_s, 6), "calls": dict(self.calls)}

    # ------------------------------------------------------------- pool

    def init_cache(self) -> Any:
        eng = self.engine
        # real block layout at 1-byte scale: dim 1 is the block dim the
        # lane-vector copies index, shape[3] is what block_size_of reads
        shape = (1, eng.dp * eng.num_blocks, 1, eng.block_size, 1)
        return {"k": np.zeros(shape, np.int8),
                "v": np.zeros(shape, np.int8)}

    # ----------------------------------------------------------- tokens

    def _tokens(self, keys: np.ndarray, last_tok: np.ndarray,
                pos: np.ndarray, temps: np.ndarray) -> np.ndarray:
        vocab = np.uint64(self.engine.cfg.vocab_size)
        h = (last_tok.astype(np.uint64) * _MIX_A) ^ (
            pos.astype(np.uint64) * _MIX_B)
        h_sampled = h ^ (keys[:, 0].astype(np.uint64) << np.uint64(17)) ^ (
            keys[:, 1].astype(np.uint64))
        h = np.where(temps <= 0.0, h, h_sampled)
        return (h % vocab).astype(np.int32)

    @staticmethod
    def _advance(keys: np.ndarray) -> np.ndarray:
        mixed = (keys[:, 0].astype(np.uint64) * _LCG_MUL
                 + keys[:, 1].astype(np.uint64) * _LCG_ADD + np.uint64(1))
        out = np.empty_like(keys)
        out[:, 0] = (mixed >> np.uint64(32)).astype(np.uint32)
        out[:, 1] = (mixed & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        return out

    # --------------------------------------------------------- programs

    def step_fn(self) -> Callable:
        def step(params, cache, tokens, tables, offsets, last_idx, samp,
                 keys):
            B, S = tokens.shape
            self._charge("prefill" if S > 1 else "decode", B, S)
            rows = np.arange(B)
            last_tok = tokens[rows, last_idx]
            tok = self._tokens(keys, last_tok, offsets + last_idx,
                               samp["temperature"])
            return cache, tok, self._advance(keys)

        return step

    def verify_fn(self) -> Callable:
        def verify(params, cache, tokens, tables, offsets, samp, keys):
            B, K1 = tokens.shape
            K = K1 - 1
            self._charge("verify", B, K1)
            temps = samp["temperature"]
            # greedy chain: position j's token from (token_j, offset+j) —
            # the same function the plain step uses, so temp-0 verify is
            # exact against non-speculative stub decode
            ver = np.stack([
                self._tokens(keys, tokens[:, j], offsets + j, temps)
                for j in range(K1)], axis=1).astype(np.int32)
            acc = (tokens[:, 1:] == ver[:, :K]).astype(np.int32)
            # sampled rows accept nothing (the stub models no acceptance
            # distribution); their correction token folds in the key
            sampled = temps > 0.0
            acc[sampled] = 0
            return cache, ver, acc, self._advance(keys)

        return verify

    def cow_fn(self) -> Callable:
        def cow(cache, src, dst):
            self._charge("cow", len(src))
            for leaf in (cache["k"], cache["v"]):
                leaf[:, dst] = leaf[:, src]
            return cache

        return cow

    def prng_key(self, seed: int) -> np.ndarray:
        # threefry PRNGKey layout, computed host-side: [hi32, lo32]
        s = np.uint64(int(seed) & 0xFFFFFFFFFFFFFFFF)
        return np.array([s >> np.uint64(32),
                         s & np.uint64(0xFFFFFFFF)], np.uint32)


def host_migrate_blocks(
    src_cache: Dict[str, Any],
    dst_cache: Dict[str, Any],
    src_ids: np.ndarray,
    dst_ids: np.ndarray,
    compress: bool = False,
) -> Dict[str, Any]:
    """Numpy twin of :func:`~.paged_cache.migrate_blocks` for host-only
    pools: ``dst[:, dst_ids[i]] = src[:, src_ids[i]]`` per leaf.  The
    router selects this when the DESTINATION replica's device step is
    ``host_only`` (no jit over a numpy pytree, no compile per pool
    pair).  ``compress`` is accepted for signature parity — an int8 stub
    pool is already at wire precision, so it changes nothing, exactly
    like a quantized real pool."""
    del compress
    for name, d_leaf in dst_cache.items():
        d_leaf[:, dst_ids] = src_cache[name][:, src_ids]
    return dst_cache
