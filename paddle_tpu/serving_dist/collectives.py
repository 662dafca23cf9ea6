"""Quantized TP collectives for the sharded decode hot path.

Sharded serving (serving_dist round) pays one compute-dtype all-reduce
per half-block (row-split out_proj / fc2), one for the vocab-parallel
embedding gather, and a vocab-parallel f32 all-gather of the head
logits per sampled token — at tp degrees worth running, inter-chip
bytes are the dominant un-optimized cost of the decode loop (EQuARX,
PAPERS.md: XLA-level quantized all-reduce reaches ~2x collective
speedup at negligible quality loss; the training side already ships
`distributed.collective.quantized_all_reduce` for DCN gradient rings —
this module is the serving analogue, inside the jitted decode programs).

Mechanism: every quantized collective is an explicit `shard_map` seam
over the mesh's `mp` axis, so the SPMD partitioner has zero freedom
inside it (the r14 lesson — the pinned toolchain miscompiles when the
sort/argmax pipeline is left shardable; an explicit per-device body
cannot be re-partitioned):

  * `matmul_psum` — the row-split projections' reduction. Each shard
    computes its partial [rows, E] product, quantizes it with
    PER-CHUNK symmetric absmax scales (chunk = the E/tp slice that
    all_to_all routes to its owning shard; int4-group mode additionally
    groups scales every `int4_group` lanes and packs two codes per
    byte), ships codes+scales via all_to_all, dequantizes and SUMS IN
    f32 on the owner (one quantization error per value, not log(n)),
    re-quantizes the reduced chunk once, and all_gathers codes+scales
    back. Wire bytes: 2*(n-1)/n * rows*E at 1 (int8) or 0.5 (int4)
    byte/element + scales, vs 2*(n-1)/n * rows*E * 2 (bf16) — ~0.5x /
    ~0.25x plus a few percent of scales.
  * `embed_psum` — the vocab-parallel embedding's psum, same wire
    format: each shard gathers the token rows its vocab slice holds
    (others contribute zeros) and the partials reduce quantized.
  * `greedy_tokens` — the all-greedy fast path never ships logits at
    all: each shard argmaxes its OWN vocab slice and the shards
    exchange (max, global index) pairs — 8 bytes per row per peer
    instead of 4*V/tp; the combine reproduces `jnp.argmax`'s
    first-index tie-break exactly, so this seam is LOSSLESS (the
    greedy token equals the one computed from gathered f32 logits).
  * `gather_logits` — sampled/penalty modes and return_logits
    dispatches need the full [rows, V] row; the codes+scales
    all-gather ships 1 (0.5) byte/element instead of f32's 4.

What is NOT quantized: the dp-axis traffic (pure placement — bitwise,
no values cross a reduction), the block-table/host-input broadcasts,
and any collective XLA inserts outside these seams. A mesh whose tp
does not divide the vocab keeps its logits replicated (plan._fit
dropped the wte sharding) — the logits seams then trace to the
identity and account zero bytes, exactly like the baseline.

Byte accounting is HOST-SIDE and analytic: the wire formulas below
mirror the seam implementations element-for-element, and the decoder
increments `serving_collective_bytes_total{collective,dtype}` per
dispatch for BOTH the path actually traced and the bf16 baseline the
same dispatch would have shipped, so a bench record's bytes ratio
needs no device instrumentation.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from ..observability import metrics as _metrics

MODES = ("int8", "int4g")

# collective names the byte accounting + metrics label with
ROW_PSUM = "row_psum"
EMBED_PSUM = "embed_psum"
LOGITS_GATHER = "logits_gather"
LOGITS_ARGMAX = "logits_argmax"

_SCALE_BYTES = 4  # scales ship f32

_m_collective_bytes = _metrics.counter(
    "serving_collective_bytes_total",
    "analytic per-device wire bytes of the sharded decode collectives "
    "(dtype=baseline is what the unquantized collectives would ship "
    "for the same dispatches)",
    labelnames=("collective", "dtype"))


def record_wire_bytes(bytes_by_key):
    """Emit one dispatch's {(collective, dtype): bytes} accounting to
    the process-wide metrics registry (one bool check when telemetry
    is off — the PagedDecoder keeps its own window dict regardless)."""
    if not _metrics.enabled():
        return
    for (name, dtype), nbytes in bytes_by_key.items():
        _m_collective_bytes.labels(collective=name, dtype=dtype).inc(
            nbytes)


def _require(cond, msg):
    if not cond:
        raise ValueError(msg)


def normalize_collective_quant(mode):
    """Eager validation of the `collective_quant` config value (None
    passes through: the exact pre-round program)."""
    if mode is not None and mode not in MODES:
        raise ValueError(
            f"ShardedEngineConfig.collective_quant={mode!r} must be one "
            f"of {(None,) + MODES}")
    return mode


# ---------------------------------------------------------------------------
# quantize / dequantize primitives (pure jnp; shard_map bodies call these)
# ---------------------------------------------------------------------------

def group_size(width, group):
    """Effective scale-group width: the configured group snapped to a
    divisor of `width` (gcd — worst case per-element scales, never a
    ragged tail)."""
    return math.gcd(int(width), int(group)) or 1


def encode_int8(x, group=None):
    """[..., C] -> (int8 codes [..., C], f32 scales [..., C/g]).
    Symmetric absmax per scale group; group=None means ONE scale per
    trailing vector (the per-chunk layout of the psum wire)."""
    import jax.numpy as jnp

    C = x.shape[-1]
    g = C if group is None else group_size(C, group)
    xg = x.astype(jnp.float32).reshape(x.shape[:-1] + (C // g, g))
    amax = jnp.max(jnp.abs(xg), axis=-1, keepdims=True)
    sc = jnp.maximum(amax, 1e-12) / 127.0
    codes = jnp.clip(jnp.round(xg / sc), -127, 127).astype(jnp.int8)
    return codes.reshape(x.shape), sc.squeeze(-1)


def decode_int8(codes, scales, group=None):
    """Inverse of encode_int8 -> f32."""
    import jax.numpy as jnp

    C = codes.shape[-1]
    g = C if group is None else group_size(C, group)
    cg = codes.reshape(codes.shape[:-1] + (C // g, g))
    return (cg.astype(jnp.float32)
            * scales[..., None]).reshape(codes.shape)


def encode_int4(x, group):
    """[..., C] -> (packed uint8 codes [..., C/2], f32 scales
    [..., C/g]). Two's-complement nibbles in [-7, 7], two per byte
    (even lane low nibble); C must be even (every seam width here is a
    multiple of tp and of 2)."""
    import jax.numpy as jnp

    C = x.shape[-1]
    _require(C % 2 == 0, f"int4 packing needs an even width, got {C}")
    g = group_size(C, group)
    xg = x.astype(jnp.float32).reshape(x.shape[:-1] + (C // g, g))
    amax = jnp.max(jnp.abs(xg), axis=-1, keepdims=True)
    sc = jnp.maximum(amax, 1e-12) / 7.0
    q = jnp.clip(jnp.round(xg / sc), -7, 7).astype(
        jnp.int8).reshape(x.shape)
    packed = ((q[..., 0::2] & 0xF)
              | ((q[..., 1::2] & 0xF) << 4)).astype(jnp.uint8)
    return packed, sc.squeeze(-1)


def decode_int4(packed, scales, group, width):
    """Inverse of encode_int4 -> f32 [..., width]."""
    import jax.numpy as jnp

    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    q = jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (width,))
    g = group_size(width, group)
    qg = q.reshape(q.shape[:-1] + (width // g, g))
    return (qg.astype(jnp.float32)
            * scales[..., None]).reshape(q.shape)


def _wire_encode(x, mode, group):
    """(codes, scales) for one wire hop. int8 ships one scale per
    trailing vector (per-chunk); int4g ships group scales and packed
    nibbles."""
    if mode == "int8":
        return encode_int8(x)
    return encode_int4(x, group)


def _wire_decode(codes, scales, mode, group, width):
    if mode == "int8":
        return decode_int8(codes, scales)
    return decode_int4(codes, scales, group, width)


# ---------------------------------------------------------------------------
# wire-byte formulas (host-side accounting — mirror the seams exactly)
# ---------------------------------------------------------------------------

def _hop_bytes(nvec, width, mode, group):
    """Bytes of codes+scales for `nvec` vectors of `width` lanes on ONE
    wire hop (before the (n-1)/n routing fraction)."""
    if mode == "int8":
        return nvec * width + nvec * _SCALE_BYTES
    g = group_size(width, group)
    return nvec * width // 2 + nvec * (width // g) * _SCALE_BYTES


def psum_wire_bytes(nrows, width, tp, mode, group, base_itemsize):
    """(actual, baseline) per-device wire bytes of ONE all-reduce over
    a [nrows, width] partial. Baseline = the ring all-reduce XLA
    emits: 2*(n-1)/n * data. Quantized = all_to_all (codes+scales of
    tp chunks) + all_gather of the re-quantized owned chunk."""
    if tp <= 1:
        return 0, 0
    base = int(2 * (tp - 1) * nrows * width * base_itemsize // tp)
    if mode is None:
        return base, base
    chunk = width // tp
    # phase 1: all_to_all routes (tp-1)/tp of the [nrows, tp, chunk]
    # code+scale set; phase 2: each shard sends its reduced chunk's
    # codes+scales to tp-1 peers
    p1 = _hop_bytes(nrows * tp, chunk, mode, group) * (tp - 1) // tp
    p2 = _hop_bytes(nrows, chunk, mode, group) * (tp - 1)
    return int(p1 + p2), base


def gather_wire_bytes(nrows, vocab, tp, mode, group):
    """(actual, baseline) per-device wire bytes of the vocab-parallel
    logits all-gather ([nrows, vocab] f32 baseline; codes+scales of
    the local [nrows, vocab/tp] slice quantized)."""
    if tp <= 1 or vocab % tp:
        return 0, 0
    base = int((tp - 1) * nrows * vocab * 4 // tp)
    if mode is None:
        return base, base
    return int(_hop_bytes(nrows, vocab // tp, mode, group)
               * (tp - 1)), base


def argmax_wire_bytes(nrows, vocab, tp):
    """(actual, baseline) per-device wire bytes of the greedy
    fast path: each row ships one (f32 max, int32 global index) pair
    per peer instead of the f32 logits row."""
    if tp <= 1 or vocab % tp:
        return 0, 0
    base = int((tp - 1) * nrows * vocab * 4 // tp)
    return int((tp - 1) * nrows * 8), base


# ---------------------------------------------------------------------------
# the CollectiveQuant bundle (static, hashable — part of every builder key)
# ---------------------------------------------------------------------------

@dataclass(frozen=True, eq=False)
class CollectiveQuant:
    """The static quantized-collectives spec one sharded PagedDecoder
    traces with. Hashable (jax Mesh hashes structurally), so the
    lru-cached program builders key on it like on `mode`/`kv_quant`;
    `None` stays the exact pre-round program."""

    mode: str            # "int8" | "int4g"
    tp: int
    mesh: object         # jax Mesh
    group: int = 32      # int4-group scale width
    axis: str = "mp"

    def __post_init__(self):
        _require(self.mode in MODES,
                 f"CollectiveQuant.mode={self.mode!r} must be one of "
                 f"{MODES}")
        _require(isinstance(self.tp, int) and self.tp > 1,
                 f"CollectiveQuant.tp={self.tp!r} must be an int > 1 "
                 f"(tp=1 has no wire — pass collective_quant=None)")
        _require(isinstance(self.group, int) and self.group >= 1,
                 f"CollectiveQuant.group={self.group!r} must be a "
                 f"positive int")

    # Mesh objects compare by devices+axes; include shape in the hash
    # but not the device list (two servers on equal meshes share jits
    # via DecodeShardings equality anyway — this only needs to be
    # stable and hashable)
    def __hash__(self):
        return hash((self.mode, self.tp, self.group, self.axis,
                     tuple(dict(self.mesh.shape).items())))

    def __eq__(self, other):
        return (isinstance(other, CollectiveQuant)
                and self.mode == other.mode and self.tp == other.tp
                and self.group == other.group and self.axis == other.axis
                and self.mesh == other.mesh)

    # -- traced seams ---------------------------------------------------

    def _shard_map(self, body, in_specs, out_specs):
        from jax import shard_map

        return shard_map(body, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _quantized_psum(self, x):
        """shard_map BODY helper: all_to_all + dequant-sum + all_gather
        of one per-shard partial [..., width]; returns the reduced
        array in x.dtype."""
        import jax
        import jax.numpy as jnp

        n, ax = self.tp, self.axis
        width = x.shape[-1]
        lead = x.shape[:-1]
        xr = jnp.moveaxis(x.reshape(lead + (n, width // n)), -2, 0)
        codes, sc = _wire_encode(xr, self.mode, self.group)
        codes = jax.lax.all_to_all(codes, ax, split_axis=0,
                                   concat_axis=0)
        sc = jax.lax.all_to_all(sc, ax, split_axis=0, concat_axis=0)
        part = _wire_decode(codes, sc, self.mode, self.group,
                            width // n).sum(axis=0)
        codes2, sc2 = _wire_encode(part, self.mode, self.group)
        codes2 = jax.lax.all_gather(codes2, ax)
        sc2 = jax.lax.all_gather(sc2, ax)
        full = _wire_decode(codes2, sc2, self.mode, self.group,
                            width // n)
        return jnp.moveaxis(full, 0, -2).reshape(
            lead + (width,)).astype(x.dtype)

    def _specs(self, ndim_x, P):
        """(x_spec, w_spec, out_spec) for a row-split matmul seam over
        an [..., K] activation and a [K, N] weight."""
        x_spec = P(*([None] * (ndim_x - 1) + [self.axis]))
        w_spec = P(self.axis, None)
        out_spec = P(*([None] * ndim_x))
        return x_spec, w_spec, out_spec

    def matmul_psum(self, x, w, cast=None):
        """Row-split projection with a quantized reduction: x [..., K]
        (K sharded over mp), w [K, N] (row-sharded) -> replicated
        [..., N]. `cast` applies to the weight INSIDE the body (the
        W8A16 codes->compute-dtype cast of `matw`); the per-output-
        column scale epilogue stays outside (it applies after the
        reduction — replicated, free)."""
        from jax.sharding import PartitionSpec as P

        x_spec, w_spec, out_spec = self._specs(x.ndim, P)

        def body(x_loc, w_loc):
            if cast is not None:
                w_loc = w_loc.astype(cast)
            return self._quantized_psum(x_loc @ w_loc)

        return self._shard_map(body, (x_spec, w_spec), out_spec)(x, w)

    def embed_psum(self, ids, table, scales=None, dt=None):
        """Vocab-parallel embedding with a quantized psum: ids [...]
        int32, table [V, E] row-sharded over mp (W8A16: int8 codes plus
        per-row `scales` [V]). Each shard contributes the rows its
        vocab slice holds; the partials reduce through the quantized
        wire. Returns [..., E] replicated in `dt` (or table dtype)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        id_spec = P(*([None] * ids.ndim))
        tab_spec = P(self.axis, None)
        out_spec = P(*([None] * (ids.ndim + 1)))
        args = (ids, table) + ((scales,) if scales is not None else ())
        in_specs = (id_spec, tab_spec) + (
            (P(self.axis),) if scales is not None else ())

        def body(ids_loc, tab_loc, *rest):
            vs = tab_loc.shape[0]
            off = jax.lax.axis_index(self.axis) * vs
            loc = ids_loc - off
            ok = (loc >= 0) & (loc < vs)
            rows = tab_loc[jnp.clip(loc, 0, vs - 1)]
            if rest:  # W8A16 codes: dequant the gathered rows
                rows = rows.astype(dt) \
                    * rest[0][jnp.clip(loc, 0, vs - 1)][..., None] \
                    .astype(dt)
            elif dt is not None:
                rows = rows.astype(dt)
            part = jnp.where(ok[..., None], rows, 0)
            return self._quantized_psum(part)

        return self._shard_map(body, in_specs, out_spec)(*args)

    def greedy_tokens(self, logits):
        """LOSSLESS vocab-parallel argmax over mp-sharded [R, V] f32
        logits: per-shard (max, first-index) pairs exchanged instead of
        logits rows. Reproduces `jnp.argmax`'s first-index tie-break
        (global max, then smallest global index). Caller guarantees
        V % tp == 0 (checked at trace time by `vocab_sharded`)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        V = logits.shape[-1]

        def body(lg):
            vs = lg.shape[-1]
            gi = (jnp.argmax(lg, axis=-1)
                  + jax.lax.axis_index(self.axis) * vs)
            vals = jax.lax.all_gather(jnp.max(lg, axis=-1), self.axis)
            idxs = jax.lax.all_gather(gi, self.axis)        # [n, R]
            gmax = vals.max(axis=0)
            cand = jnp.where(vals >= gmax[None], idxs, V)
            return cand.min(axis=0).astype(jnp.int32)

        return self._shard_map(body, (P(None, self.axis),),
                               P(None))(logits)

    def gather_logits(self, logits):
        """Quantized vocab-parallel all-gather: mp-sharded [R, V] f32
        -> replicated f32 through the codes+scales wire (per-row
        scales under int8, per-group under int4g). Caller guarantees
        V % tp == 0."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        n = self.tp
        V = logits.shape[-1]

        def body(lg):
            codes, sc = _wire_encode(lg, self.mode, self.group)
            codes = jax.lax.all_gather(codes, self.axis)
            sc = jax.lax.all_gather(sc, self.axis)
            full = _wire_decode(codes, sc, self.mode, self.group,
                                V // n)
            return jnp.concatenate([full[i] for i in range(n)],
                                   axis=-1)

        return self._shard_map(body, (P(None, self.axis),),
                               P(None, None))(logits)

    def vocab_sharded(self, vocab):
        """Whether the plan actually shards this vocab (plan._fit drops
        indivisible dims to replicated — then there is no logits
        collective to quantize OR to count)."""
        return int(vocab) % self.tp == 0


def build_collective_quant(cfg, mesh):
    """The engine-side constructor: a ShardedEngineConfig whose
    `collective_quant` is set and whose tp > 1 yields a CollectiveQuant
    over the server's mesh; anything else yields None (tp=1 has no
    inter-chip wire — quantizing it would only perturb numerics)."""
    mode = normalize_collective_quant(
        getattr(cfg, "collective_quant", None))
    if mode is None or cfg.tp <= 1:
        return None
    return CollectiveQuant(mode=mode, tp=cfg.tp, mesh=mesh,
                           group=getattr(cfg, "int4_group", 32))


# ---------------------------------------------------------------------------
# per-dispatch accounting (host side)
# ---------------------------------------------------------------------------

def dispatch_wire_bytes(*, spec, vocab, tp, mode, group, trunk_rows,
                        logit_rows, greedy_fast, base_itemsize):
    """{(collective, dtype): bytes} one decode dispatch ships, for the
    ACTUAL path (`mode` None = unquantized) alongside the bf16
    baseline under the "baseline" dtype key. trunk_rows = token rows
    through the transformer trunk (2L row psums of [rows, E] plus one
    embed psum); logit_rows = head readout rows; greedy_fast = the
    all-greedy argmax seam replaced the logits gather."""
    L, _H, _Dh, E, _eps, _tied = spec
    out = {}
    dtype = mode or "base"

    def add(name, actual, baseline):
        if baseline or actual:
            out[(name, dtype)] = out.get((name, dtype), 0) + actual
            out[(name, "baseline")] = (out.get((name, "baseline"), 0)
                                       + baseline)

    a, b = psum_wire_bytes(trunk_rows, E, tp, mode, group,
                           base_itemsize)
    add(ROW_PSUM, a * 2 * L, b * 2 * L)
    if int(vocab) % tp == 0:
        a, b = psum_wire_bytes(trunk_rows, E, tp, mode, group,
                               base_itemsize)
        add(EMBED_PSUM, a, b)
        if greedy_fast and mode is not None:
            a, b = argmax_wire_bytes(logit_rows, vocab, tp)
            add(LOGITS_ARGMAX, a, b)
        else:
            a, b = gather_wire_bytes(logit_rows, vocab, tp, mode, group)
            add(LOGITS_GATHER, a, b)
    return out
