"""Device-mesh topology registry — the TPU-native analogue of the reference's
process-group topology (``torchdistpackage/dist/process_topo.py:53-262``).

The reference builds NCCL process groups from an *ordered* config such as
``[('data', 4), ('pipe', 2), ('tensor', 2)]`` where the **last** listed dim has
stride 1 — i.e. consecutive ranks, i.e. intra-node placement (its
``gen_groups`` stride algorithm, process_topo.py:32-51).  On TPU the natural
substrate is a named :class:`jax.sharding.Mesh`: we reshape the device list in
C order over the configured sizes, so the last-listed axis likewise gets
ICI-adjacent devices.  Every group-getter / predicate of the reference maps to
a mesh-axis query; collectives use axis *names* inside ``shard_map`` instead of
group handles.

**Physical placement** (the reference's core value prop — its stride algorithm
deliberately decides which group lands intra-node, process_topo.py:32-51,
motivated at Intro.md:15-44): on real TPU devices the enumeration order of
``jax.devices()`` does NOT guarantee that a C-order reshape puts an axis's
members on ICI neighbors (2D/3D torus wraparound, multi-slice DCN).  So
:meth:`ParallelContext.setup_process_groups` routes TPU device lists through
``jax.experimental.mesh_utils``:

- single slice: ``create_device_mesh(sizes, devices)`` assigns logical axes to
  physical ICI torus axes from device *coords* — the last-listed (stride-1)
  axis gets the most network-local placement, honoring the ordered-config
  contract on real hardware, not just in enumeration order;
- multi-slice (devices carrying distinct ``slice_index``, i.e. a DCN-connected
  multislice job): ``create_hybrid_device_mesh`` — the DCN dimension is
  absorbed by the OUTERMOST config axes (largest stride = cross-slice, exactly
  the reference's outer-axes-cross-node semantics), overridable per axis via
  ``dcn_config``.

Non-TPU devices (CPU sim, tests) keep the plain C-order reshape, so the
8-device CI sim and the driver dryrun behave exactly as before.


Key translations (reference -> here):

- ``tpc.setup_process_groups(cfg)``   -> :meth:`ParallelContext.setup_process_groups`
- ``dist.new_group(ranks)``           -> (not needed — axes name sub-meshes implicitly)
- ``tpc.get_group('tensor')``         -> axis name ``'tensor'`` (pass to psum etc.)
- ``tpc.get_tp_rank()``               -> :meth:`axis_index` (traced) or
                                         :meth:`process_axis_index` (host-side)
- auto "model" group (process_topo.py:112-116) -> :meth:`model_axes` (tuple of
  all non-data axis names; psum over a tuple == all-reduce over the flattened
  group, so no explicit transpose construction is required)
- ``tpc.build_moe_groups`` (process_topo.py:118-143) -> :meth:`build_moe_mesh`
  — a *view* mesh over the same devices with the data axis factored into
  ``('moe_dp', 'moe_ep')``, ep innermost (matching the reference's contiguous
  ep ranks within each dp group)
- ``setup_node_groups`` (node_group.py:3-32) -> :meth:`build_hybrid_mesh`
  — data axis factored into ``('data_inter', 'data_intra')`` for hybrid
  (intra-node) ZeRO sharding
- ``test_comm()`` (process_topo.py:267-316) -> :func:`test_comm` smoke test.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax

from jax.lax import axis_size
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AxisName = Union[str, Tuple[str, ...]]

# Canonical axis names (the reference's group "modes").
DATA_AXIS = "data"
TENSOR_AXIS = "tensor"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "moe_ep"
MOE_DATA_AXIS = "moe_dp"
CONTEXT_AXIS = "context"


def _slice_ids(devices: Sequence) -> List[int]:
    """Distinct ``slice_index`` values (sorted).  Devices without the
    attribute (or with ``None``) count as one slice — single-slice TPU jobs
    and CPU sims don't set it."""
    ids = {getattr(d, "slice_index", None) for d in devices}
    if ids == {None}:
        return [0]
    if None in ids:
        raise ValueError(
            "mixed device list: some devices carry slice_index, some don't"
        )
    return sorted(ids)


def _derive_dcn_shape(
    names: Sequence[str],
    sizes: Sequence[int],
    num_slices: int,
    dcn_config: Optional[Dict[str, int]],
) -> List[int]:
    """Per-axis DCN factors (product == num_slices).

    Explicit ``dcn_config`` wins; otherwise the slice count is absorbed
    greedily from the LEFT (outermost axes — largest stride — go cross-slice,
    the reference's outer-axes-cross-node layout, process_topo.py:32-51)."""
    if dcn_config is not None:
        unknown = set(dcn_config) - set(names)
        if unknown:
            raise ValueError(f"dcn_config axes {unknown} not in config {list(names)}")
        shape = [int(dcn_config.get(nm, 1)) for nm in names]
        if math.prod(shape) != num_slices:
            raise ValueError(
                f"dcn_config {dcn_config} multiplies to {math.prod(shape)}, "
                f"but the device list spans {num_slices} slices"
            )
        for nm, s, d in zip(names, sizes, shape):
            if s % d != 0:
                raise ValueError(
                    f"axis {nm!r} of size {s} not divisible by its DCN factor {d}"
                )
        return shape
    shape = []
    remaining = num_slices
    for s in sizes:
        d = math.gcd(remaining, s)
        shape.append(d)
        remaining //= d
    if remaining != 1:
        raise ValueError(
            f"cannot distribute {num_slices} slices over axis sizes "
            f"{list(sizes)}; pass dcn_config explicitly"
        )
    if len(shape) > 1 and shape[-1] != 1:
        # the greedy fallback would put DCN on the stride-1 axis — the one
        # the ordered-config contract promises is the most network-LOCAL
        # (e.g. [('data', 2), ('tensor', 8)] on 4 slices: TP collectives
        # would silently cross DCN every layer).  Never silently: the
        # operator must say so explicitly.
        raise ValueError(
            f"distributing {num_slices} slices over {list(zip(names, sizes))} "
            f"would put a DCN factor on the innermost axis "
            f"{names[-1]!r} (derived {shape}); if that is intended, pass "
            f"dcn_config explicitly"
        )
    return shape


def _assign_devices(
    names: Sequence[str],
    sizes: Sequence[int],
    devices: Sequence,
    topology: str,
    dcn_config: Optional[Dict[str, int]],
) -> np.ndarray:
    """Device ndarray of shape ``sizes`` with physical-topology-aware
    placement on TPU (see module docstring), C-order reshape otherwise."""
    if topology not in ("auto", "ici", "flat"):
        raise ValueError(f"topology must be 'auto'|'ici'|'flat', got {topology!r}")
    is_tpu = (
        getattr(devices[-1], "platform", None) == "tpu"
        and hasattr(devices[-1], "coords")
    )
    if topology == "flat" or (topology == "auto" and not is_tpu):
        if dcn_config:
            raise ValueError("dcn_config requires the topology-aware path")
        return np.array(devices, dtype=object).reshape(sizes)
    if not is_tpu:
        raise ValueError(
            "topology='ici' needs TPU devices with coords; got "
            f"{getattr(devices[-1], 'platform', None)!r}"
        )
    from jax.experimental import mesh_utils

    slices = _slice_ids(devices)
    if len(slices) > 1:
        dcn_shape = _derive_dcn_shape(names, sizes, len(slices), dcn_config)
        per_slice = [s // d for s, d in zip(sizes, dcn_shape)]
        return mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn_shape, devices, allow_split_physical_axes=True
        )
    if dcn_config and math.prod(dcn_config.values()) != 1:
        raise ValueError(
            f"dcn_config {dcn_config} given but the device list is a single slice"
        )
    return mesh_utils.create_device_mesh(
        sizes, devices, allow_split_physical_axes=True
    )


class ParallelContext:
    """Singleton-ish registry of the device mesh and its named-axis views.

    Unlike the reference (``SingletonMeta``, process_topo.py:6-26) we allow
    explicit construction for tests, but ship a module-level ``tpc`` instance
    as the canonical entry point, mirroring ``torch_parallel_context``
    (process_topo.py:262).
    """

    def __init__(self) -> None:
        self._reset()

    # ------------------------------------------------------------------ setup

    def _reset(self) -> None:
        self.mesh: Optional[Mesh] = None
        self._config: List[Tuple[str, int]] = []
        self._views: Dict[str, Mesh] = {}
        self._devices: Optional[np.ndarray] = None  # flat, C-order of config

    def reset(self) -> None:
        """Drop all state (tests / re-setup)."""
        self._reset()

    @property
    def is_initialized(self) -> bool:
        return self.mesh is not None

    def setup_process_groups(
        self,
        config: Sequence[Tuple[str, int]],
        devices: Optional[Sequence[jax.Device]] = None,
        topology: str = "auto",
        dcn_config: Optional[Dict[str, int]] = None,
    ) -> Mesh:
        """Build the base mesh from an ordered ``[(axis, size), ...]`` config.

        Semantics match ``ProcessTopology.setup_process_groups``
        (process_topo.py:70-116): the last-listed axis has stride 1, i.e. its
        members are consecutive devices (ICI-adjacent on TPU, intra-node on
        GPU clusters).  Example::

            tpc.setup_process_groups([('data', 2), ('pipe', 2), ('tensor', 2)])

        gives tensor groups over adjacent device pairs, pipe groups with
        stride 2 and data groups with stride 4 — identical rank layouts to the
        reference's docstring example (process_topo.py:72-90).

        Axis sizes may use ``-1`` for at most one axis, which absorbs the
        remaining device count (convenience over the reference).

        ``topology`` selects the physical placement strategy:

        - ``'auto'`` (default): TPU devices with coords go through
          ``mesh_utils`` (torus-aware, multi-slice-aware); anything else
          (CPU sim) is a plain C-order reshape.
        - ``'ici'``: require the torus-aware path (raise on non-TPU devices).
        - ``'flat'``: force the C-order reshape even on TPU (the pre-round-5
          behavior; also the escape hatch for exotic device lists).

        ``dcn_config`` (multi-slice only) maps axis name -> how many slices
        that axis spans, e.g. ``{'data': 4}`` for pure dp-over-DCN.  The
        product must equal the number of slices; unlisted axes span 1.  By
        default the OUTERMOST config axes absorb the slice count greedily —
        the reference's outer-axes-are-cross-node semantics
        (process_topo.py:32-51)."""
        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        n = len(devices)

        names = [str(d) for d, _ in config]
        sizes = [int(s) for _, s in config]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate axis names in config: {names}")
        if sizes.count(-1) > 1:
            raise ValueError("at most one axis size may be -1")
        if -1 in sizes:
            known = math.prod(s for s in sizes if s != -1)
            if n % known != 0:
                raise ValueError(f"cannot infer -1 axis: {n} devices, known product {known}")
            sizes[sizes.index(-1)] = n // known
        if math.prod(sizes) != n:
            raise ValueError(f"config sizes {sizes} do not multiply to device count {n}")

        arr = _assign_devices(names, sizes, devices, topology, dcn_config)
        self._config = list(zip(names, sizes))
        # flat logical order (C-order of the assigned mesh): every view mesh
        # factors THIS order, so moe/hybrid views inherit the physical
        # placement
        self._devices = arr.reshape(-1)
        self.mesh = Mesh(arr, axis_names=tuple(names))
        self._views = {"default": self.mesh}
        return self.mesh

    # Convenience alias matching JAX vocabulary.
    setup_mesh = setup_process_groups

    def _require_mesh(self) -> Mesh:
        if self.mesh is None:
            raise RuntimeError("ParallelContext not initialized — call setup_process_groups first")
        return self.mesh

    # ------------------------------------------------------------- view meshes

    def build_view(
        self,
        view_name: str,
        split_axis: str,
        sub_names: Tuple[str, str],
        inner_size: int,
    ) -> Mesh:
        """Generic axis factoring: a new Mesh over the *same* devices with
        ``split_axis`` factored into ``(outer, inner)`` where the inner axis
        has consecutive devices.  psum over ``sub_names`` is identical to psum
        over the original axis, so components using different views compose.
        """
        mesh = self._require_mesh()
        if split_axis not in mesh.axis_names:
            raise ValueError(f"axis {split_axis!r} not in mesh axes {mesh.axis_names}")
        size = mesh.shape[split_axis]
        if size % inner_size != 0:
            raise ValueError(f"axis {split_axis!r} of size {size} not divisible by {inner_size}")
        outer = size // inner_size
        new_names: List[str] = []
        new_sizes: List[int] = []
        for name in mesh.axis_names:
            if name == split_axis:
                new_names.extend(sub_names)
                new_sizes.extend([outer, inner_size])
            else:
                new_names.append(name)
                new_sizes.append(mesh.shape[name])
        view = Mesh(self._devices.reshape(new_sizes), axis_names=tuple(new_names))
        self._views[view_name] = view
        return view

    def build_moe_mesh(
        self,
        moe_dp_size: Optional[int] = None,
        moe_ep_size: Optional[int] = None,
    ) -> Mesh:
        """MoE view: data axis -> ('moe_dp', 'moe_ep'), ep innermost.

        Mirrors ``build_moe_groups`` (process_topo.py:118-143): expert-parallel
        ranks are contiguous within each data group (so EP all-to-all rides
        ICI), same-expert replicas form the strided moe_dp groups.
        """
        dp = self.get_dp_size()
        if moe_dp_size and not moe_ep_size:
            if dp % moe_dp_size != 0:
                raise ValueError(f"moe_dp_size {moe_dp_size} does not divide dp size {dp}")
            moe_ep_size = dp // moe_dp_size
        elif moe_ep_size and not moe_dp_size:
            if dp % moe_ep_size != 0:
                raise ValueError(f"moe_ep_size {moe_ep_size} does not divide dp size {dp}")
            moe_dp_size = dp // moe_ep_size
        elif moe_dp_size and moe_ep_size:
            if moe_dp_size * moe_ep_size != dp:
                raise ValueError(f"moe_dp {moe_dp_size} * moe_ep {moe_ep_size} != dp {dp}")
        else:
            raise ValueError("need moe_dp_size or moe_ep_size")
        return self.build_view("moe", DATA_AXIS, (MOE_DATA_AXIS, EXPERT_AXIS), moe_ep_size)

    def build_hybrid_mesh(self, intra_size: int) -> Mesh:
        """Hybrid-ZeRO view: data -> ('data_inter', 'data_intra'), intra
        innermost (ICI-local).  Analogue of ``setup_node_groups``
        (node_group.py:3-32) which builds one group per physical node so ZeRO
        shards only intra-node (Intro.md:69-77)."""
        return self.build_view("hybrid", DATA_AXIS, ("data_inter", "data_intra"), intra_size)

    def get_view(self, name: str = "default") -> Mesh:
        self._require_mesh()
        if name not in self._views:
            raise KeyError(f"mesh view {name!r} not built; have {list(self._views)}")
        return self._views[name]

    # --------------------------------------------------------------- axis info

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return self._require_mesh().axis_names

    def is_mode_inited(self, mode: str) -> bool:
        """Reference semantics (process_topo.py:236-237): axis exists AND has
        size > 1 (in any built view)."""
        if self.mesh is None:
            return False
        for mesh in self._views.values():
            if mode in mesh.axis_names and mesh.shape[mode] > 1:
                return True
        if mode == "model":
            return self.get_mp_size() > 1
        return False

    def _axis_mesh(self, mode: str) -> Mesh:
        for mesh in self._views.values():
            if mode in mesh.axis_names:
                return mesh
        raise KeyError(f"axis {mode!r} not found in any mesh view")

    def get_group_size(self, mode: str) -> int:
        if mode == "global":
            return self._require_mesh().size
        if mode == "model":
            return self.get_mp_size()
        mesh = self._axis_mesh(mode)
        return mesh.shape[mode]

    def get_tp_size(self) -> int:
        return self.get_group_size(TENSOR_AXIS) if self._has_axis(TENSOR_AXIS) else 1

    def get_pp_size(self) -> int:
        return self.get_group_size(PIPE_AXIS) if self._has_axis(PIPE_AXIS) else 1

    def get_dp_size(self) -> int:
        return self.get_group_size(DATA_AXIS) if self._has_axis(DATA_AXIS) else 1

    def get_mp_size(self) -> int:
        """Model-parallel size = product of all non-data base axes — the
        transpose of the data groups, auto-derived like process_topo.py:112-116."""
        mesh = self._require_mesh()
        return math.prod(mesh.shape[a] for a in mesh.axis_names if a != DATA_AXIS)

    def _has_axis(self, mode: str) -> bool:
        try:
            self._axis_mesh(mode)
            return True
        except KeyError:
            return False

    def num_slices(self) -> int:
        """Number of DCN-connected slices the mesh spans (1 on single-slice
        jobs and CPU sims)."""
        return len(_slice_ids(list(self._require_mesh().devices.flat)))

    def model_axes(self) -> Tuple[str, ...]:
        """Axis names forming the auto-derived 'model' group.  Collectives
        accept tuples of axis names, so ``psum(x, tpc.model_axes())`` is the
        all-reduce over the reference's 'model' group."""
        mesh = self._require_mesh()
        return tuple(a for a in mesh.axis_names if a != DATA_AXIS)

    def data_axes(self, view: str = "default") -> Tuple[str, ...]:
        """Axis names whose flattened product is the data-parallel group in the
        given view ('default' -> ('data',); 'moe' -> ('moe_dp', 'moe_ep'))."""
        mesh = self.get_view(view)
        base = {DATA_AXIS, MOE_DATA_AXIS, EXPERT_AXIS, "data_inter", "data_intra"}
        return tuple(a for a in mesh.axis_names if a in base)

    # ---------------------------------------------------- traced (SPMD) queries

    @staticmethod
    def axis_index(mode: AxisName):
        """Rank within an axis — traced; valid inside shard_map/pjit-manual.
        Analogue of ``get_group_rank`` (process_topo.py:155-156)."""
        return jax.lax.axis_index(mode)

    def get_tp_rank(self):
        return self.axis_index(TENSOR_AXIS)

    def get_pp_rank(self):
        return self.axis_index(PIPE_AXIS)

    def get_dp_rank(self):
        return self.axis_index(DATA_AXIS)

    def is_first_in_group(self, mode: AxisName):
        return jax.lax.axis_index(mode) == 0

    def is_last_in_group(self, mode: AxisName):
        return jax.lax.axis_index(mode) == axis_size(mode) - 1

    def is_first_in_pipeline_group(self):
        return self.is_first_in_group(PIPE_AXIS)

    def is_last_in_pipeline_group(self):
        return self.is_last_in_group(PIPE_AXIS)

    def is_using_pp(self) -> bool:
        """Host-side — analogue of ``is_using_pp`` (process_topo.py:264-265)."""
        return self.is_mode_inited(PIPE_AXIS)

    # -------------------------------------------------------- host-side coords

    def device_coords(self, device: Optional[jax.Device] = None) -> Dict[str, int]:
        """Mesh coordinates of a device (host-side introspection; replaces the
        reference's global-rank bookkeeping)."""
        mesh = self._require_mesh()
        if device is None:
            device = mesh.devices.flat[0]
        arr = mesh.devices
        pos = np.argwhere(arr == device)
        if len(pos) == 0:
            raise ValueError(f"device {device} not in mesh")
        return dict(zip(mesh.axis_names, (int(i) for i in pos[0])))

    def process_axis_index(self, mode: str) -> int:
        """Axis index of *this process's* first local device — host-side rank
        analogue for multi-host code (checkpoint naming etc.)."""
        mesh = self._axis_mesh(mode)
        local = [d for d in mesh.devices.flat if d.process_index == jax.process_index()]
        if not local:
            raise RuntimeError(
                f"process {jax.process_index()} has no local device in the mesh; "
                "process_axis_index is only meaningful on participating hosts"
            )
        arr = mesh.devices
        pos = np.argwhere(arr == local[0])[0]
        return int(pos[list(mesh.axis_names).index(mode)])

    def ranks_in_axis(self, mode: str) -> List[List[int]]:
        """All groups of flat device indices for an axis — analogue of
        ``all_ranks`` (process_topo.py:242-246); mainly for tests/debug."""
        mesh = self._axis_mesh(mode)
        flat_index = {d: i for i, d in enumerate(self._devices)}
        ax = list(mesh.axis_names).index(mode)
        moved = np.moveaxis(mesh.devices, ax, -1).reshape(-1, mesh.shape[mode])
        return [[flat_index[d] for d in row] for row in moved]

    # ------------------------------------------------------------ spec helpers

    def spec(self, *names: Optional[AxisName]) -> PartitionSpec:
        return PartitionSpec(*names)

    def sharding(self, *names: Optional[AxisName], view: str = "default") -> NamedSharding:
        return NamedSharding(self.get_view(view), PartitionSpec(*names))


# The canonical context — analogue of ``torch_parallel_context``
# (process_topo.py:262).
tpc = ParallelContext()


def is_using_pp() -> bool:
    return tpc.is_using_pp()


def check_placement(tree, mesh: Mesh) -> None:
    """Raise unless every array leaf of ``tree`` lives on exactly the
    devices of ``mesh`` — an array left on the default device would still
    compute, on one chip, while the mesh's other chips sat idle."""
    want = set(mesh.devices.flat)
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        have = leaf.sharding.device_set
        if have != want:
            raise AssertionError(
                f"{jax.tree_util.keystr(path)} is on {sorted(d.id for d in have)}, "
                f"the mesh is {sorted(d.id for d in want)}")


def test_comm(mesh: Optional[Mesh] = None) -> Dict[str, bool]:
    """Smoke-test collectives over every mesh axis — analogue of
    ``test_comm`` (process_topo.py:267-316).

    Runs a psum (all-reduce), all_gather and ring ppermute over each axis of
    the mesh inside one jitted shard_map and checks the numerics, returning
    ``{axis: ok}``.  Unlike the reference this is deterministic and asserts
    values, not just liveness.

    The value checks run INSIDE the computation and come back as one
    replicated ok-count per axis, so the function works unchanged on
    multi-process meshes (a per-shard fetch of the collective outputs would
    touch non-addressable shards; a replicated scalar is always local —
    executed cross-process in ``tests/test_multiprocess.py``).
    """
    from jax import shard_map
    import jax.numpy as jnp

    if mesh is None:
        mesh = tpc._require_mesh()
    results: Dict[str, bool] = {}
    for axis in mesh.axis_names:
        n = mesh.shape[axis]

        def body(x):
            total = jax.lax.psum(x, axis)                     # all_reduce
            gathered = jax.lax.all_gather(x, axis, tiled=True)  # all_gather
            nxt = jax.lax.ppermute(                           # ring send/recv
                x, axis, [(i, (i + 1) % n) for i in range(n)]
            )
            i = jax.lax.axis_index(axis)
            prev = ((i - 1) % n).astype(x.dtype)
            ok = (
                jnp.all(total == float(sum(range(n))))
                & jnp.all(gathered[:, 0] == jnp.arange(n, dtype=x.dtype))
                & jnp.all(nxt == prev)
            )
            # every shard must pass -> count == n, replicated over the axis
            return jax.lax.psum(ok.astype(jnp.int32), axis)

        spec = PartitionSpec(axis)
        x = jnp.arange(n, dtype=jnp.float32).reshape(n, 1)
        fn = jax.jit(
            shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=PartitionSpec())
        )
        ok = int(fn(x)) == n
        results[axis] = ok
        if not ok:
            raise AssertionError(f"test_comm failed for axis {axis!r}")
    return results
