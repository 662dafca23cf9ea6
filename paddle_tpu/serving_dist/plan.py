"""The GPT-2 decode sharding plan: flat param names -> PartitionSpec.

The placement is the PROVEN training TP plan (models/gpt2_hybrid.py /
parallel/api.py Megatron rules) transcribed onto the decode programs'
flat naming ("h.{i}.qkv_proj.weight", ...):

  * column-split (output dim over mp): qkv_proj, fc1 — their biases and
    per-output-column int8 scales shard with the columns;
  * row-split (contraction dim over mp): out_proj, fc2 — XLA inserts
    the ONE all-reduce per half-block after each, exactly the psum the
    training `_stage_fn` places; their biases/scales are replicated
    (they apply after the reduction);
  * vocab-parallel embedding + tied head: wte rows over mp — the embed
    is a sharded gather, the head's [B, V]-sharded logits are
    all-gathered before the sampling pipeline (argmax/top-k need the
    full vocab row; the training path keeps them sharded because CE
    only needs psum'd softmax statistics — serving pays the gather, the
    placement the ISSUE names);
  * everything else (wpe, layer norms, row-split biases) replicated.

The W8A16 key convention is honored: "name::w8c" codes shard like
"name", "name::w8s" per-output-column scales shard like the weight's
LAST dim.  The KV pool shards its HEAD axis over mp (each device holds
its heads' slice of every block — block tables stay replicated host
state) and optionally its BLOCK axis over dp; int8 pools shard codes
and per-vector scales in lockstep.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.api import tp_spec_for

W8_CODES, W8_SCALES = "::w8c", "::w8s"


def _base_weight_spec(name, ndim):
    """Spec of a base (non-suffixed) decode param name."""
    if name == "wte.weight":
        return P("mp", *([None] * (ndim - 1)))  # vocab-parallel
    if name == "wpe.weight" or ".ln_" in name or name.startswith("ln_f"):
        return P()
    if name.endswith(".bias"):
        # biases follow their weight's output columns: column-split
        # projections get sharded biases, row-split ones replicated
        w = tp_spec_for(name[:-len(".bias")] + ".weight", 2)
        return P("mp") if tuple(w) and tuple(w)[-1] == "mp" else P()
    return tp_spec_for(name, ndim)  # Megatron column/row rules


def decode_spec_for(name, ndim):
    """PartitionSpec for one flat decode param (handles the int8 key
    convention: codes shard like the weight, per-output-column scales
    like its last dim)."""
    if name.endswith(W8_CODES):
        return _base_weight_spec(name[:-len(W8_CODES)], ndim)
    if name.endswith(W8_SCALES):
        base = name[:-len(W8_SCALES)]
        if base == "wte.weight":
            # embedding scales are per VOCAB ROW (the quantization
            # channel), not per column — they shard with the rows
            return P("mp", *([None] * (ndim - 1)))
        w = _base_weight_spec(base, max(ndim + 1, 2))
        last = tuple(w)[-1] if tuple(w) else None
        return P(*([None] * (ndim - 1) + [last]))
    return _base_weight_spec(name, ndim)


def _fit(mesh, spec, shape):
    """Drop spec axes whose mesh size doesn't divide the dim (explicit
    NamedSharding placement requires divisibility; GPT-2's 50257 vocab
    is the canonical offender).  The leaf just stays replicated on that
    dim — correctness is placement-independent, and XLA may still
    shard the computation internally."""
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    return P(*[ax if ax is not None and shape[i] % mesh.shape[ax] == 0
               else None for i, ax in enumerate(entries)])


def param_shardings(mesh, params):
    """dict name -> NamedSharding for one decode param dict (base or
    W8A16-quantized keys alike); indivisible dims fall back to
    replicated per-leaf."""
    return {name: NamedSharding(mesh, _fit(
        mesh, decode_spec_for(name, v.ndim), v.shape))
        for name, v in params.items()}


def kv_pool_specs(kv_dtype=None):
    """(k_blocks, v_blocks) sharding-spec pytrees for the pool arrays:
    [L, num_blocks, block_size, H*Dh] with heads over mp (they are the
    major half of the minor axis, so a shard holds whole heads) and
    blocks over dp.  For an int8 pool the per-vector scale buffer
    [L, num_blocks, block_size, H] shards identically, so codes and
    scales stay in lockstep under every block operation."""
    codes = P(None, "dp", None, "mp")
    if kv_dtype == "int8":
        from ..inference.kv_quant import QuantizedKV

        spec = QuantizedKV(codes, codes)
    elif kv_dtype is None:
        spec = codes
    else:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r} "
                         "(supported: None, 'int8')")
    return spec, spec


class DecodeShardings:
    """The sharding bundle one sharded PagedDecoder jits with: per-name
    param shardings, the kc/vc pool sharding pytree, and the replicated
    sharding every host-side dispatch input/output is pinned to.

    HASHABLE (param shardings held as a sorted item tuple; Mesh and
    NamedSharding hash structurally), so the explicit-sharding jits in
    nn/decode are cached process-wide per bundle — two servers on
    equal meshes share compiled programs instead of re-jitting."""

    __slots__ = ("mesh", "_params_items", "kv", "rep")

    def __init__(self, mesh, params, kv, rep):
        self.mesh = mesh
        self._params_items = tuple(sorted(params.items()))
        self.kv = kv
        self.rep = rep

    @property
    def params(self):
        return dict(self._params_items)

    @property
    def shard_label(self):
        """The `shard` label the ops plane's compile metrics carry
        (serving_xla_compiles_total{..., shard=}): the mesh shape in
        axis=size form, e.g. "mp2xdp1" — so a fleet scraping several
        mesh configs can tell whose jit cache went cold.  A sequence-
        parallel mesh (long-context round) appends "xsp{n}"; sp=1
        keeps the exact pre-round label so existing dashboards and
        the r14 gauge assertions never see a rename."""
        shape = dict(self.mesh.shape)
        label = f"mp{shape.get('mp', 1)}xdp{shape.get('dp', 1)}"
        if shape.get("sp", 1) > 1:
            label += f"xsp{shape['sp']}"
        return label

    def _key(self):
        return (self.mesh, self._params_items, self.kv, self.rep)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return (isinstance(other, DecodeShardings)
                and self._key() == other._key())


def build_decode_shardings(mesh, params, kv_dtype=None):
    """Assemble the DecodeShardings bundle for one server's param dict
    (call AFTER quantize_weights so the ::w8c/::w8s keys are in)."""
    k_spec, _ = kv_pool_specs(kv_dtype)
    kv = jax.tree.map(lambda sp: NamedSharding(mesh, sp), k_spec,
                      is_leaf=lambda x: isinstance(x, P))
    return DecodeShardings(mesh, param_shardings(mesh, params), kv,
                           NamedSharding(mesh, P()))


def place_decode_params(mesh, params):
    """device_put the param dict with the plan's shardings (the
    explicit placement half; the jit's in_shardings re-assert it)."""
    sh = param_shardings(mesh, params)
    return {name: jax.device_put(v, sh[name])
            for name, v in params.items()}


def place_kv_pool(mesh, cache):
    """device_put the cache's K/V pool arrays with the per-shard block
    layout (heads over mp, blocks over dp).  Host bookkeeping — block
    tables, refcounts, the prefix index, retention — is untouched: the
    whole point is that every shard holds its slice of every block, so
    block INDICES mean the same thing on every device."""
    k_spec, v_spec = kv_pool_specs(cache.kv_dtype)
    as_sh = (lambda spec: jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), spec,
        is_leaf=lambda x: isinstance(x, P)))
    cache.swap_arrays(jax.device_put(cache.k_blocks, as_sh(k_spec)),
                      jax.device_put(cache.v_blocks, as_sh(v_spec)))
