"""Mesh construction + named-dim -> PartitionSpec layout rules.

The TPU-native replacement for the reference's auto-derived mtf mesh
(`mesh_shape = "b:<tpu_size/heads>,h:<heads>"`, `layout = "batch:b,heads:h"`,
/root/reference/src/dataclass.py:247-252) and SimdMeshImpl lowering: dim
*names* map to mesh axes; anonymized (``_``-prefixed) dims never match a rule
and are therefore replicated, exactly like the reference's anonymize trick —
but here XLA GSPMD materialises the collectives.

Axes: 'data' (batch), 'model' (heads), optional 'sequence' (long-context
sequence sharding — new capability, reference has none, SURVEY.md §5.7).
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..config import ModelParameter
from ..telemetry import memory
from .dims import Dim
from .tensor import NamedTensor, nt

#: canonical mesh-axis names.  Code OUTSIDE this module / ``parallel/`` /
#: ``config.py`` must reference axes through these constants — the
#: ``mesh-axis-literal`` AST rule (analysis/ast_lint.py) flags hardcoded
#: axis-name strings so an axis rename cannot silently strand a
#: PartitionSpec or a ``mesh.shape.get("...")`` probe.
DATA_AXIS = "data"
PIPE_AXIS = "pipe"
MODEL_AXIS = "model"
SEQUENCE_AXIS = "sequence"
#: mesh construction order (build_mesh below)
MESH_AXES = (DATA_AXIS, PIPE_AXIS, MODEL_AXIS, SEQUENCE_AXIS)


def build_mesh(params: ModelParameter,
               devices: typing.Optional[typing.Sequence[jax.Device]] = None) -> Mesh:
    """Mesh from the config's derived mesh_shape, adapted to the devices
    actually present (the config targets a pod; tests run on 8 virtual CPU
    devices; bench runs on 1 chip)."""
    if devices is None:
        devices = jax.devices()
    ndev = len(devices)
    shape = dict(params.mesh_shape)
    model = shape.get("model", 1)
    seq = shape.get("sequence", 1)
    pipe = shape.get("pipe", 1)
    while model * seq * pipe > ndev and model > 1:
        model //= 2
    while model * seq * pipe > ndev and seq > 1:
        seq //= 2
    while model * seq * pipe > ndev and pipe > 1:
        pipe //= 2
    data = max(1, ndev // (model * seq * pipe))
    axes, sizes = [], []
    for name, size in (("data", data), ("pipe", pipe), ("model", model),
                       ("sequence", seq)):
        if name in shape or name == "data":
            axes.append(name)
            sizes.append(size)
    if int(np.prod(sizes)) != ndev:
        # devices[:prod] would leave the rest idle with nothing said
        raise ValueError(
            f"mesh {dict(zip(axes, sizes))} (from config mesh_shape "
            f"{shape}) covers {int(np.prod(sizes))} of {ndev} devices — "
            "set mesh_shape_override / tpu_size to a layout that uses "
            "every device, or pass the devices to use")
    return Mesh(np.asarray(devices).reshape(sizes), tuple(axes))


def shard_geometry(mesh) -> typing.Tuple[int, typing.Any]:
    """``(per-device shard divisor, a device)`` for capacity estimates —
    activation-sized arrays shard over every data / model / sequence axis;
    ``(1, None)`` without a mesh."""
    shards = 1
    device = None
    if mesh is not None and getattr(mesh, "devices", None) is not None:
        for axis in (DATA_AXIS, MODEL_AXIS, SEQUENCE_AXIS):
            shards *= mesh.shape.get(axis, 1)
        device = np.asarray(mesh.devices).flat[0]
    return shards, device


def placement_report(variables: typing.Mapping[str, jax.Array],
                     mesh: typing.Optional[Mesh]) -> str:
    """One start-up line saying where the parameters actually are: the mesh,
    how many of this process's devices hold parameter shards, and each
    device's ``bytes_in_use`` as the runtime reports it (None on backends
    without memory_stats).  Raises if a local device holds no shard — a
    mesh that quietly uses fewer chips than the host has is a broken run,
    not a slow one."""
    holding = set()
    for v in variables.values():
        holding |= v.sharding.device_set
    local = jax.local_devices()
    idle = [d.id for d in local if d not in holding]
    in_use = {d.id: (stats or {}).get("in_use")
              for d, stats in memory.read(local)}
    line = (f"placement: mesh={dict(mesh.shape) if mesh is not None else None}"
            f" parameter shards on {len(local) - len(idle)}/{len(local)} "
            f"local devices; bytes_in_use={in_use}")
    if idle:
        raise RuntimeError(f"{line} — devices {idle} hold no parameters")
    return line


def inference_mesh(params: ModelParameter,
                   devices: typing.Optional[typing.Sequence[jax.Device]] = None
                   ) -> Mesh:
    """Serving mesh: the config's device layout with the 'pipe' and
    'sequence' axes folded into 'data'.

    Incremental decode has no pipeline schedule and no ring-attention
    schedule (KV caches hold the full anonymized sequence), so those axes
    would idle; folding them into 'data' keeps every device of the training
    topology participating — parameters and KV caches shard over 'model'
    (tensor parallelism), batches over 'data'.  The reference served
    inference through the same SimdMeshImpl mesh as training
    (/root/reference/src/run/run.py:200-308)."""
    mesh = build_mesh(params, devices)
    fold = mesh.shape.get("pipe", 1) * mesh.shape.get("sequence", 1)
    if fold == 1:
        return mesh
    sizes = dict(mesh.shape)
    data = sizes.get("data", 1) * fold
    # build_mesh orders axes (data, pipe, model, sequence); a plain reshape
    # would interleave 'model' between the folded axes, so transpose the
    # device array to (data, pipe, sequence, model) first
    order = [mesh.axis_names.index(a)
             for a in ("data", "pipe", "sequence", "model")
             if a in mesh.axis_names]
    dev = np.transpose(mesh.devices, order)
    model = sizes.get("model", 1)
    if "model" in mesh.axis_names:
        return Mesh(dev.reshape(data, model), ("data", "model"))
    return Mesh(dev.reshape(data), ("data",))


def spec_for_dims(params: ModelParameter, dims: typing.Sequence[Dim],
                  mesh: Mesh) -> PartitionSpec:
    """PartitionSpec from layout rules; each mesh axis used at most once."""
    used: set = set()
    entries = []
    for d in dims:
        axis = params.layout.get(d.name)
        if axis is not None and axis in mesh.axis_names and axis not in used \
                and d.size % mesh.shape[axis] == 0:
            entries.append(axis)
            used.add(axis)
        else:
            entries.append(None)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def named_sharding(params: ModelParameter, dims: typing.Sequence[Dim],
                   mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, spec_for_dims(params, dims, mesh))


def shard_params(params: ModelParameter, variables: typing.Dict[str, jax.Array],
                 param_dims: typing.Dict[str, tuple], mesh: Mesh
                 ) -> typing.Dict[str, jax.Array]:
    """device_put every variable with its layout-derived NamedSharding
    (weights carrying a 'heads' dim shard over 'model', like mtf layout
    rules sharded every heads-bearing weight)."""
    out = {}
    for name, value in variables.items():
        dims = param_dims.get(name, ())
        sharding = named_sharding(params, dims, mesh)
        out[name] = jax.device_put(value, sharding)
    return out


@functools.lru_cache(maxsize=8)
def process_data_slice(mesh: Mesh) -> typing.Tuple[int, int]:
    """(slice_index, slice_count) of the global batch this process must feed.

    The 'data' mesh axis may span fewer process groups than there are
    processes (e.g. full model parallelism: data=1, model across hosts —
    every process must then feed IDENTICAL full batches), or more than one
    row-block per process.  Derived from which data-axis coordinates this
    process's devices actually occupy; cached per mesh (called every step
    from shard_batch — the device scan is O(all devices))."""
    if "data" not in mesh.axis_names:
        return 0, 1
    axis = mesh.axis_names.index("data")
    pid = jax.process_index()
    coords = sorted({idx[axis] for idx, dev in np.ndenumerate(mesh.devices)
                     if dev.process_index == pid})
    if not coords:
        return 0, 1
    data_size = mesh.shape["data"]
    span = len(coords)
    if coords != list(range(coords[0], coords[0] + span)):
        raise ValueError(
            f"non-contiguous data coords for process {pid}: {coords}")
    # unaligned layouts would let two processes claim the same slice while
    # another goes unfed — refuse instead of silently training on wrong data
    if coords[0] % span or data_size % span:
        raise ValueError(f"process {pid} data coords {coords} not "
                         f"block-aligned in data axis of size {data_size}")
    slice_count = max(1, data_size // span)
    return coords[0] // span, slice_count


def place_tree(template_tree, host_tree):
    """Lay host (numpy) arrays out with the shardings of a template tree of
    live jax Arrays.  Works in multi-controller runs where a plain
    ``device_put`` cannot target non-addressable devices: every process holds
    the full host value and contributes the shards it owns
    (``make_array_from_callback``)."""
    def place(template, host):
        host = np.asarray(host)
        if not isinstance(template, jax.Array) or not template.committed:
            # an uncommitted template (single-device state built with
            # jnp.asarray) gets an uncommitted copy: a committed one lowers
            # to a DIFFERENT module (explicit single-device sharding
            # annotations), so a resumed run would miss the persistent
            # compile cache its first run filled
            return jnp.asarray(host)
        assert template.shape == host.shape, (template.shape, host.shape)
        return jax.make_array_from_callback(
            host.shape, template.sharding, lambda idx: host[idx])
    return jax.tree_util.tree_map(place, template_tree, host_tree)


def shard_batch(params: ModelParameter, batch: typing.Dict[str, jax.Array],
                mesh: Mesh, batch_axis: typing.Optional[int] = None
                ) -> typing.Dict[str, jax.Array]:
    """Batch arrays shard along their leading (batch) axis over 'data'.

    Single-process: a plain ``device_put`` with the NamedSharding.  Multi-host
    (``jax.process_count() > 1``): every process holds only its per-process
    slice of the global batch (the train loop feeds
    ``slice_index=process_index``), so the slices are assembled into one
    global array via ``jax.make_array_from_process_local_data`` — the named
    equivalent of the reference's per-host infeed placement
    (/root/reference/src/run/dataloader_placement.py:153-227).  A bare
    ``device_put`` here would treat each process's slice as the full global
    batch: wrong data on every host but host 0.
    """
    out = {}
    nproc = jax.process_count()
    # the number of distinct batch slices across processes follows the
    # data-axis process layout, NOT the process count: with full model
    # parallelism (data axis inside each host group) every process feeds
    # identical full batches
    _, slice_count = process_data_slice(mesh) if nproc > 1 else (0, 1)
    # under macro-batching the leading axis is the macro index; the batch
    # axis (the one sharded over 'data' and split across processes) is 1.
    # Callers feeding micro-shaped batches under a macro config (the eval
    # pass) say so via ``batch_axis=0``
    if batch_axis is None:
        batch_axis = 1 if params.macro_batching > 1 else 0
    for key, value in batch.items():
        entries: typing.List[typing.Optional[str]] = [None] * value.ndim
        global_shape = list(value.shape)
        if "data" in mesh.axis_names and value.ndim > batch_axis:
            if nproc > 1:
                global_shape[batch_axis] *= slice_count
            if global_shape[batch_axis] % mesh.shape["data"] == 0:
                entries[batch_axis] = "data"
            elif nproc > 1:
                # a replicated multi-host assembly is unservable: each process
                # holds a distinct slice, so fail here with a clear message
                # rather than deep inside make_array_from_process_local_data
                raise ValueError(
                    f"global batch {global_shape[batch_axis]} for {key!r} is "
                    f"not divisible by the 'data' mesh axis "
                    f"({mesh.shape['data']}) across {nproc} processes")
        sharding = NamedSharding(mesh, PartitionSpec(*entries))
        if nproc > 1:
            out[key] = jax.make_array_from_process_local_data(
                sharding, np.asarray(value), tuple(global_shape))
        else:
            out[key] = jax.device_put(value, sharding)
    return out


def with_constraint(t: NamedTensor, params: ModelParameter,
                    mesh: typing.Optional[Mesh]) -> NamedTensor:
    """Annotate a named tensor's sharding inside jit (activation layouts)."""
    if mesh is None:
        return t
    spec = spec_for_dims(params, t.dims, mesh)
    return nt(jax.lax.with_sharding_constraint(t.data, NamedSharding(mesh, spec)),
              t.dims)
