"""graft-wire: block-quantized gradient collectives (EQuARX-style).

The reference's only collective is the fp32 gradient all-reduce (reference
train.py:233 — DDP's bucketed backward hooks); our explicit ZeRO-1
decomposition (train/step.py) still moves full-precision bytes every step.
EQuARX (arxiv 2506.17615) shows a block-quantized all-reduce — int8
payloads with per-block scales, quantized at the edge of every wire hop —
recovers ~3x of that traffic at negligible quality cost. This module is
the drop-in layer: ``wire_psum_scatter`` / ``wire_psum`` /
``wire_all_gather`` replace the raw ``lax`` collectives inside the step's
data-manual region, dispatching on a :class:`WireConfig`:

- ``compress="none"``: byte-identical to the raw collective (the default;
  every existing budget/equivalence bar is unchanged).
- ``compress="int8-block"``: payloads quantize to int8 with one bf16
  scale per ``block_size`` elements. int8 partial sums cannot ride an
  in-network reduction (overflow, and every shard carries its own
  scales), so the quantized reduce-scatter is recomposed as
  *split-by-destination -> quantize -> all-to-all(s8) -> dequantize ->
  f32 local sum* — same wire direction and volume as a ring
  reduce-scatter, ~1/4 the bytes (1 payload byte + 2/block_size scale
  bytes per element instead of 4). The quantized psum is that
  reduce-scatter followed by a quantized all-gather of the reduced
  chunk, so the plain-DP fallback path compresses too.

What is deliberately NOT quantized by default:

- Leaves below ``min_size`` elements: a handful of int8 blocks plus
  scales for a bias saves nothing and costs latency (mirrors the ZeRO-1
  ``opt_shard_min_size`` floor rationale).
- The ZeRO-1 param re-replication all-gather. ``state.params`` after the
  step IS the gathered buffer that feeds the next optimizer update, so a
  lossy gather corrupts the f32 master weights a little more every step
  — unlike gradient noise, that error is never averaged away.
  ``param_gather="bf16"`` (or ``"int8-block"``) opts the gather into
  compression via :func:`replicate_params` for bf16-tolerant runs; the
  default keeps it exact (see README "Wire-efficient collectives").

Stochastic rounding (``stochastic_rounding=True`` + a ``key``): rounds
x to ``floor(x + u)``, ``u ~ U[0,1)`` — unbiased per element, so the
quantization error of the gradient MEAN decays with the number of
contributions instead of accumulating a deterministic bias.

On TPU the uncompressed collectives (and the gather half of the
compressed ones) can route through the Pallas async bidirectional-ring
kernels (``ops/pallas/collectives.py``) when ``ring="auto"``; every
backend that cannot lower them (the 8-device fake CPU mesh the tests run
on) falls back to the XLA collective with identical numerics.

``grad_wire_report`` is the analytic accounting side: per-device
gradient-sync wire bytes per step from the param tree + partitioner +
config, the quantity the Trainer's telemetry summary carries under
``wire`` (``grad_wire_bytes_per_step``, ``wire_compression_ratio``) and
the comm-budget ``wire-int8-step``
signature gates at >= 3x (analysis/collectives.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

COMPRESS_MODES = ("none", "int8-block")
PARAM_GATHER_MODES = ("float32", "bf16", "int8-block")
RING_MODES = ("auto", "off")

# int8 symmetric range: +-127 (128 is reserved so negation stays exact)
_QMAX = 127.0


def _scoped(name: str):
    """Stamp a dispatch boundary with a ``jax.named_scope`` so every HLO
    op the collective lowers to carries the wire-layer scope in its
    metadata: the key by which a device trace or the compiled HLO's
    text attributes the op to this layer."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco

# leaves below this many ELEMENTS stay on the fp32 collective — scale
# overhead + quantize latency beat the byte savings for biases/scalars
# (same floor rationale as parallel/api.py DEFAULT_OPT_SHARD_MIN_SIZE)
DEFAULT_MIN_SIZE = 2048

# default size target (bytes of fp32 gradient) for one comm/compute
# overlap bucket when bucketing is requested without an explicit size —
# the same order as DDP's bucket_cap_mb=25 scaled to the payloads our
# dryrun/test models move (reference train.py:233: DDP's bucketed
# backward hooks are exactly this partitioning)
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class WireConfig:
    """Collective-compression policy threaded Trainer -> train/step.py.

    ``compress`` selects the gradient-sync payload ("none" | "int8-block");
    ``block_size`` elements share one bf16 scale; ``stochastic_rounding``
    makes the quantizer unbiased (needs a key at the call site);
    ``param_gather`` opts the ZeRO-1 param re-replication into a lossy
    gather ("float32" keeps it exact — module docstring for why that is
    the default); ``ring`` gates the Pallas async ring kernels ("auto"
    uses them where they lower, "off" forces the XLA collectives);
    ``min_size`` is the element floor below which leaves keep fp32.

    ``bucket_bytes`` > 0 switches the gradient sync from one collective
    per param leaf to FUSED size-targeted buckets (``plan_buckets`` /
    ``sync_grads``): leaves are concatenated in reverse trace order and
    each bucket moves as ONE collective with an independent dataflow
    chain, so the XLA latency-hiding scheduler can issue bucket k's
    reduce-scatter while the backward segment producing bucket k+1 is
    still computing — the comm/compute overlap DDP's bucketed hooks get
    for free. 0 (the default) keeps the inline per-leaf path.
    """

    compress: str = "none"
    block_size: int = 256
    stochastic_rounding: bool = False
    param_gather: str = "float32"
    ring: str = "auto"
    min_size: int = DEFAULT_MIN_SIZE
    bucket_bytes: int = 0

    def __post_init__(self):
        if self.compress not in COMPRESS_MODES:
            raise ValueError(
                f"WireConfig.compress must be one of {COMPRESS_MODES}, "
                f"got {self.compress!r}"
            )
        if self.param_gather not in PARAM_GATHER_MODES:
            raise ValueError(
                f"WireConfig.param_gather must be one of "
                f"{PARAM_GATHER_MODES}, got {self.param_gather!r}"
            )
        if self.ring not in RING_MODES:
            raise ValueError(
                f"WireConfig.ring must be one of {RING_MODES}, "
                f"got {self.ring!r}"
            )
        if self.block_size < 1:
            raise ValueError(
                f"WireConfig.block_size must be >= 1, got {self.block_size}"
            )
        if self.bucket_bytes < 0:
            raise ValueError(
                f"WireConfig.bucket_bytes must be >= 0, got "
                f"{self.bucket_bytes}"
            )

    @property
    def active(self) -> bool:
        """Whether any wire surface differs from the raw collectives."""
        return (
            self.compress != "none"
            or self.param_gather != "float32"
            or self.bucketed
        )

    @property
    def bucketed(self) -> bool:
        """Whether gradient sync runs the fused bucketed issue path."""
        return self.bucket_bytes > 0

    def compresses(self, n_elements: int) -> bool:
        """Whether a leaf of this many elements gets the int8 payload."""
        return self.compress == "int8-block" and n_elements >= self.min_size


# -- block quantizer -------------------------------------------------------


def quantize_blocks(x, block_size: int, key=None):
    """(values int8, scales bf16) with one scale per ``block_size`` elems.

    ``x`` flattens row-major; the tail block zero-pads (the pad elements
    quantize to 0 and are sliced off on dequantize). A ``key`` switches
    round-to-nearest to unbiased stochastic rounding. All-zero blocks get
    scale 0 and round-trip exactly.
    """
    rows, pad = _pad_rows(x.reshape(1, -1), block_size)
    q, scales = _quantize_rows(rows, block_size, key)
    return q[0], scales[0]


def dequantize_blocks(q, scales, shape, dtype=jnp.float32):
    """Inverse of :func:`quantize_blocks` back to ``shape``."""
    n = 1
    for d in shape:
        n *= int(d)
    flat = _dequantize_rows(q[None], scales[None], n, dtype)
    return flat[0].reshape(shape)


def _pad_rows(rows, block_size: int):
    """(rows padded to a block multiple on axis 1, pad length)."""
    pad = (-rows.shape[1]) % block_size
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    return rows, pad


def _quantize_rows(rows, block_size: int, key=None):
    """Per-row block quantization: (R, N) f32 -> (R, B, block) s8 +
    (R, B, 1) bf16 scales, N a multiple of block_size."""
    r, n = rows.shape
    blocks = rows.astype(jnp.float32).reshape(r, n // block_size, block_size)
    amax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    scales = (amax / _QMAX).astype(jnp.bfloat16)
    # zero blocks: scale 0, inverse 0 — values quantize to 0 exactly
    inv = jnp.where(amax > 0.0, _QMAX / jnp.maximum(amax, 1e-30), 0.0)
    scaled = blocks * inv
    if key is not None:
        # unbiased: floor(x + u), u ~ U[0,1) per element
        u = jax.random.uniform(key, scaled.shape, jnp.float32)
        rounded = jnp.floor(scaled + u)
    else:
        rounded = jnp.round(scaled)
    q = jnp.clip(rounded, -_QMAX, _QMAX).astype(jnp.int8)
    return q, scales


def _dequantize_rows(q, scales, n: int, dtype=jnp.float32):
    """(R, B, block) s8 + (R, B, 1) bf16 -> (R, n) ``dtype`` (pad cut)."""
    vals = q.astype(jnp.float32) * scales.astype(jnp.float32)
    return vals.reshape(q.shape[0], -1)[:, :n].astype(dtype)


# -- collective drop-ins (call INSIDE a shard_map manual over ``axis``) ----


def _split_key(key, n: int):
    if key is None:
        return (None,) * n
    return tuple(jax.random.split(key, n))


@_scoped("wire_psum_scatter")
def wire_psum_scatter(x, axis_name: str, *, scatter_dimension: int,
                      config: Optional[WireConfig] = None, key=None):
    """Drop-in ``lax.psum_scatter(..., tiled=True)`` with optional int8
    payloads.

    Quantized form (module docstring): split ``x`` into one chunk per
    shard along ``scatter_dimension``, quantize each chunk, exchange via
    ``all_to_all`` (s8 values + bf16 scales), dequantize the received
    contributions and sum them in f32. Result matches the tiled
    psum_scatter layout exactly; values differ only by the per-block
    quantization error of each contribution.
    """
    config = config or WireConfig()
    if not config.compresses(x.size):
        return lax.psum_scatter(
            x, axis_name, scatter_dimension=scatter_dimension, tiled=True
        )
    d = lax.axis_size(axis_name)
    dim = scatter_dimension
    if x.shape[dim] % d:
        raise ValueError(
            f"scatter dimension {dim} of shape {x.shape} must divide the "
            f"'{axis_name}' span {d}"
        )
    chunk = x.shape[dim] // d
    parts = jnp.moveaxis(
        x.reshape(x.shape[:dim] + (d, chunk) + x.shape[dim + 1:]), dim, 0
    )  # (d, ...) — one chunk per destination shard
    chunk_shape = parts.shape[1:]
    rows, _ = _pad_rows(parts.reshape(d, -1), config.block_size)
    q, scales = _quantize_rows(
        rows, config.block_size, key if config.stochastic_rounding else None
    )
    # the wire hop: each shard sends its quantized chunk j to shard j —
    # the exact byte flow of a ring reduce-scatter, at s8 + scales
    q = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    scales = lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0)
    n = 1
    for s in chunk_shape:
        n *= int(s)
    got = _dequantize_rows(q, scales, n)  # (d, n): one row per source
    return jnp.sum(got, axis=0).reshape(chunk_shape)


@_scoped("wire_all_gather")
def wire_all_gather(x, axis_name: str, *, gather_dimension: int = 0,
                    config: Optional[WireConfig] = None, key=None):
    """Drop-in tiled ``lax.all_gather`` with optional int8 payloads.

    Quantized form: quantize the local shard once, gather the s8 values
    and bf16 scales (via the Pallas ring kernel where it lowers,
    ``ring="auto"``), dequantize every shard's contribution locally.
    """
    config = config or WireConfig()
    if not config.compresses(x.size):
        return _gather(x, axis_name, gather_dimension, config)
    rows, _ = _pad_rows(x.reshape(1, -1), config.block_size)
    q, scales = _quantize_rows(
        rows, config.block_size, key if config.stochastic_rounding else None
    )
    q = _gather(q, axis_name, 0, config)          # (d, B, block) s8
    scales = _gather(scales, axis_name, 0, config)  # (d, B, 1) bf16
    got = _dequantize_rows(q, scales, x.size, x.dtype)  # (d, local size)
    d = got.shape[0]
    parts = got.reshape((d,) + x.shape)
    return jnp.concatenate(
        [parts[i] for i in range(d)], axis=gather_dimension
    )


@_scoped("wire_psum")
def wire_psum(x, axis_name: str, *,
              config: Optional[WireConfig] = None, key=None):
    """Drop-in ``lax.psum`` with optional int8 payloads.

    Quantized form: the all-reduce decomposes exactly like a ring
    all-reduce — quantized reduce-scatter of the flattened leaf (padded
    to a shard multiple) followed by a quantized all-gather of the
    reduced chunk — so BOTH wire passes carry s8 + scales.
    """
    config = config or WireConfig()
    if not config.compresses(x.size):
        return lax.psum(x, axis_name)
    d = lax.axis_size(axis_name)
    k1, k2 = _split_key(key if config.stochastic_rounding else None, 2)
    flat = x.reshape(-1)
    pad = (-flat.size) % d
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(d, -1)  # one destination chunk per shard
    rows, _ = _pad_rows(chunks, config.block_size)
    q, scales = _quantize_rows(rows, config.block_size, k1)
    q = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    scales = lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0)
    reduced = jnp.sum(
        _dequantize_rows(q, scales, chunks.shape[1]), axis=0
    )  # this shard's fully reduced chunk, f32
    rows2, _ = _pad_rows(reduced[None], config.block_size)
    q2, scales2 = _quantize_rows(rows2, config.block_size, k2)
    q2 = _gather(q2, axis_name, 0, config)
    scales2 = _gather(scales2, axis_name, 0, config)
    full = _dequantize_rows(q2, scales2, chunks.shape[1]).reshape(-1)
    if pad:
        full = full[: x.size]
    return full.reshape(x.shape).astype(x.dtype)


def _gather(x, axis_name: str, gather_dimension: int,
            config: WireConfig, stream: int = 0):
    """Tiled all-gather, through the Pallas async ring where it lowers.

    ``stream`` selects the ring kernel's collective buffer set (one per
    overlap bucket) so concurrent bucketed gathers never share barrier
    semaphores — see ``ops/pallas/collectives.py``.
    """
    if config.ring != "off" and gather_dimension == 0:
        from distributed_pytorch_example_tpu.ops.pallas import (
            collectives as ring,
        )

        if ring.ring_supported():
            return ring.ring_all_gather(x, axis_name, stream=stream)
    return lax.all_gather(x, axis_name, axis=gather_dimension, tiled=True)


# -- bucketed gradient sync (comm/compute overlap) -------------------------


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One fused gradient-sync bucket (static — shapes only).

    ``kind`` is ``"scatter"`` (every leaf has a ZeRO-1 scatter dim; the
    bucket moves as one fused reduce-scatter) or ``"psum"`` (unsharded
    leaves; one fused all-reduce). ``leaves`` are flat
    ``tree_leaves``-order indices into the gradient tree; ``elements``
    the bucket's total element count; ``fp32_bytes`` its size metric
    (4 B/element, the pre-compression payload the size target governs);
    ``wire_bytes`` the analytic per-device ring payload of the bucket's
    collective(s) under the config that planned it.
    """

    index: int
    kind: str
    leaves: Tuple[int, ...]
    elements: int
    fp32_bytes: int
    wire_bytes: int

    def to_json(self) -> dict:
        return {
            "index": self.index,
            "kind": self.kind,
            "num_leaves": len(self.leaves),
            "elements": self.elements,
            "fp32_bytes": self.fp32_bytes,
            "wire_bytes": self.wire_bytes,
        }


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """The static bucket schedule ``sync_grads`` executes.

    ``buckets`` are in ISSUE ORDER: reverse trace order over the leaf
    list, because the backward pass produces the LAST layers' gradients
    first — bucket 0's collective can therefore launch while the
    backward segments feeding later buckets are still computing (the
    DDP bucketed-hook issue order, reference train.py:233). Purely a
    function of shapes + config, so the step build, the analytic
    reports, and the tests all derive the identical plan.
    """

    buckets: Tuple[Bucket, ...]
    bucket_bytes: int
    axis_size: int

    def to_json(self) -> dict:
        return {
            "bucket_bytes": self.bucket_bytes,
            "axis_size": self.axis_size,
            "num_buckets": len(self.buckets),
            "buckets": [b.to_json() for b in self.buckets],
        }


def plan_buckets(dims, grads, config: WireConfig, axis_size: int,
                 bucket_bytes: Optional[int] = None) -> BucketPlan:
    """Greedy size-targeted bucket assignment over gradient leaves.

    Walks the flat leaf list in REVERSE trace order (the order backward
    produces gradients), appending each leaf to the open bucket of its
    kind (scatterable vs unsharded) and sealing the bucket once its
    fp32 size reaches ``bucket_bytes``. Scatterable and unsharded
    leaves never share a bucket — they move through different
    collectives. Static: ``grads`` only needs ``.shape``/``.size``
    (ShapeDtypeStructs work), so the planner and telemetry reports run
    this without a backend.
    """
    if bucket_bytes is None:
        bucket_bytes = config.bucket_bytes or DEFAULT_BUCKET_BYTES
    is_dim_leaf = lambda d: d is None  # noqa: E731 - tree of Optional[int]
    dim_leaves = jax.tree_util.tree_leaves(dims, is_leaf=is_dim_leaf)
    leaves = jax.tree_util.tree_leaves(grads)
    if len(dim_leaves) != len(leaves):
        raise ValueError(
            f"dims/grads leaf mismatch: {len(dim_leaves)} vs {len(leaves)}"
        )
    d = max(int(axis_size), 1)
    ring_factor = (d - 1) / d if d > 1 else 0.0
    buckets = []
    open_leaves: dict = {"scatter": [], "psum": []}
    open_elems: dict = {"scatter": 0, "psum": 0}

    def seal(kind: str) -> None:
        ids = open_leaves[kind]
        if not ids:
            return
        n = open_elems[kind]
        passes = 1.0 if kind == "scatter" else 2.0  # RS vs AR (RS + AG)
        wire = passes * ring_factor * n * _bytes_per_element(config, n)
        buckets.append(Bucket(
            index=len(buckets), kind=kind, leaves=tuple(ids),
            elements=n, fp32_bytes=n * 4, wire_bytes=int(round(wire)),
        ))
        open_leaves[kind] = []
        open_elems[kind] = 0

    for i in reversed(range(len(leaves))):
        n = int(getattr(leaves[i], "size", 0) or 0)
        if n == 0:
            continue
        kind = "scatter" if dim_leaves[i] is not None else "psum"
        open_leaves[kind].append(i)
        open_elems[kind] += n
        if open_elems[kind] * 4 >= bucket_bytes:
            seal(kind)
    seal("scatter")
    seal("psum")
    return BucketPlan(
        buckets=tuple(buckets), bucket_bytes=int(bucket_bytes),
        axis_size=d,
    )


def _scatter_parts(g, dim: int, d: int):
    """((d, n/d) destination-major rows, per-shard chunk shape) of one
    scatterable leaf — row j is the flattened chunk bound for shard j,
    and the chunk shape IS the tiled ``psum_scatter`` output shape."""
    chunk = g.shape[dim] // d
    parts = jnp.moveaxis(
        g.reshape(g.shape[:dim] + (d, chunk) + g.shape[dim + 1:]), dim, 0
    )
    return parts.reshape(d, -1), parts.shape[1:]


def _reduce_scatter_rows(buf, axis_name: str, config: WireConfig,
                         stream: int) -> Any:
    """Fused fp32 reduce-scatter of a (d, n/d) destination-major buffer
    -> this shard's reduced (n/d,) row, via the Pallas async ring where
    it lowers (one buffer set per ``stream``)."""
    if config.ring != "off":
        from distributed_pytorch_example_tpu.ops.pallas import (
            collectives as ring,
        )

        if ring.ring_supported():
            return ring.ring_reduce_scatter(
                buf, axis_name, scatter_dimension=0, stream=stream
            ).reshape(-1)
    return lax.psum_scatter(
        buf, axis_name, scatter_dimension=0, tiled=True
    ).reshape(-1)


def _bucket_scatter(out, leaves, dim_leaves, bucket: Bucket,
                    axis_name: str, d: int, config: WireConfig, key,
                    scale: float) -> None:
    """Execute one fused scatter bucket: canonicalize every leaf to
    destination-major (d, n_i/d) rows, concatenate along the row, move
    the whole bucket through ONE collective, split the reduced row back
    per leaf. Quantization (when the bucket clears ``min_size``) runs
    on the concatenated buffer, so block boundaries span leaf joins —
    the parity contract is the test_zero1 trajectory bars, not
    bit-identity with the per-leaf path."""
    parts, chunk_shapes = [], []
    for i in bucket.leaves:
        rows, cs = _scatter_parts(leaves[i], dim_leaves[i], d)
        parts.append(rows)
        chunk_shapes.append(cs)
    buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    nb = buf.shape[1]
    if config.compresses(bucket.elements):
        rows, _ = _pad_rows(buf, config.block_size)
        q, scales = _quantize_rows(
            rows, config.block_size,
            key if config.stochastic_rounding else None,
        )
        q = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
        scales = lax.all_to_all(
            scales, axis_name, split_axis=0, concat_axis=0
        )
        red = jnp.sum(_dequantize_rows(q, scales, nb), axis=0)
    else:
        red = _reduce_scatter_rows(buf, axis_name, config, bucket.index)
    red = red * scale
    offset = 0
    for i, cs in zip(bucket.leaves, chunk_shapes):
        n_i = 1
        for s in cs:
            n_i *= int(s)
        out[i] = red[offset:offset + n_i].reshape(cs)
        offset += n_i


def _bucket_psum(out, leaves, bucket: Bucket, axis_name: str, d: int,
                 config: WireConfig, key, scale: float) -> None:
    """Execute one fused all-reduce bucket over the unsharded leaves:
    concatenate flattened leaves, one psum (or the quantized RS + AG
    decomposition of ``wire_psum``) over the joined buffer, split back."""
    flats = [leaves[i].reshape(-1) for i in bucket.leaves]
    flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
    n = flat.size
    if config.compresses(bucket.elements):
        k1, k2 = _split_key(
            key if config.stochastic_rounding else None, 2
        )
        padded = flat
        pad = (-n) % d
        if pad:
            padded = jnp.pad(padded, (0, pad))
        chunks = padded.reshape(d, -1)
        rows, _ = _pad_rows(chunks, config.block_size)
        q, scales = _quantize_rows(rows, config.block_size, k1)
        q = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
        scales = lax.all_to_all(
            scales, axis_name, split_axis=0, concat_axis=0
        )
        reduced = jnp.sum(
            _dequantize_rows(q, scales, chunks.shape[1]), axis=0
        )
        rows2, _ = _pad_rows(reduced[None], config.block_size)
        q2, scales2 = _quantize_rows(rows2, config.block_size, k2)
        q2 = _gather(q2, axis_name, 0, config, stream=bucket.index)
        scales2 = _gather(
            scales2, axis_name, 0, config, stream=bucket.index
        )
        full = _dequantize_rows(
            q2, scales2, chunks.shape[1]
        ).reshape(-1)
        if pad:
            full = full[:n]
    else:
        full = lax.psum(flat, axis_name)
    full = full * scale
    offset = 0
    for i in bucket.leaves:
        leaf = leaves[i]
        out[i] = full[offset:offset + leaf.size].reshape(leaf.shape)
        offset += leaf.size


def sync_grads(grads, dims, axis_name: str, *,
               config: Optional[WireConfig] = None, key=None,
               scale: float = 1.0,
               plan: Optional[BucketPlan] = None):
    """THE gradient-sync dispatcher for the data-manual train step.

    ``train/step.py`` must route every gradient collective through this
    one entry point (the ``inline-grad-sync`` graft-lint rule pins it):
    leaves with a ZeRO-1 scatter dim in ``dims`` reduce-scatter into
    the sharded-update layout, the rest all-reduce, every payload per
    the ``WireConfig``, and the result is scaled by ``scale`` (the
    global-mean factor).

    With ``config.bucket_bytes == 0`` this is the historical inline
    path — one collective per leaf, per-leaf stochastic-rounding keys in
    trace order — byte-identical to the pre-bucketing step. With a
    bucket size it executes :func:`plan_buckets`'s fused schedule: each
    bucket is one named-scope-stamped collective with its own dataflow
    chain (``wire_bucket<k>``), issued in reverse-trace order so the
    XLA latency-hiding scheduler interleaves bucket k's wire time with
    the backward compute that produces bucket k+1.
    """
    config = config or WireConfig()
    is_dim_leaf = lambda d: d is None  # noqa: E731 - tree of Optional[int]
    if not config.bucketed:
        leaf_idx = [0]  # trace-order leaf counter for per-leaf keys

        def sync(dim, g):
            k = None
            if key is not None:
                k = jax.random.fold_in(key, leaf_idx[0])
            leaf_idx[0] += 1
            if dim is not None:
                g = wire_psum_scatter(
                    g, axis_name, scatter_dimension=dim, config=config,
                    key=k,
                )
            else:
                g = wire_psum(g, axis_name, config=config, key=k)
            return g * scale

        return jax.tree_util.tree_map(
            sync, dims, grads, is_leaf=is_dim_leaf
        )

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    dim_leaves = jax.tree_util.tree_leaves(dims, is_leaf=is_dim_leaf)
    d = lax.axis_size(axis_name)
    if plan is None:
        plan = plan_buckets(dims, grads, config, d)
    out: list = list(leaves)  # zero-size leaves pass through unsynced
    for bucket in plan.buckets:
        bkey = None if key is None else jax.random.fold_in(
            key, bucket.index
        )
        with jax.named_scope(f"wire_bucket{bucket.index}"):
            if bucket.kind == "scatter":
                _bucket_scatter(
                    out, leaves, dim_leaves, bucket, axis_name, d,
                    config, bkey, scale,
                )
            else:
                _bucket_psum(
                    out, leaves, bucket, axis_name, d, config, bkey,
                    scale,
                )
    return jax.tree_util.tree_unflatten(treedef, out)


# -- ZeRO-1 param re-replication ------------------------------------------


@_scoped("wire_replicate_params")
def replicate_params(params: Any, partitioner, config: WireConfig,
                     axis_name: str = "data"):
    """Explicit wire-configured ZeRO-1 param re-replication all-gather.

    The default step re-replicates updated params with a sharding
    constraint (the implicit all-gather, train/step.py); this is the
    explicit counterpart used when ``param_gather`` opts into a lossy
    gather: each scatterable leaf enters sharded on its ZeRO-1 dim and
    all-gathers back to replicated as bf16 (or int8 blocks), so the
    gather moves 1/2 (or ~1/4) the bytes. Leaves the overlay left
    unsharded pass through unchanged. See the module docstring for why
    ``"float32"`` (the constraint path) is the default.
    """
    from jax.sharding import PartitionSpec as P


    dims = partitioner.zero1_dims(params)
    is_dim_leaf = lambda d: d is None  # noqa: E731 - tree of Optional[int]

    def spec(dim, p):
        if dim is None:
            return P()
        entries: list = [None] * p.ndim
        entries[dim] = axis_name
        return P(*entries)

    in_specs = jax.tree_util.tree_map(
        spec, dims, params, is_leaf=is_dim_leaf
    )

    def body(params):
        def gather(dim, p):
            if dim is None:
                return p
            if config.param_gather == "bf16":
                out = _gather(
                    p.astype(jnp.bfloat16), axis_name, dim, config
                )
                return out.astype(p.dtype)
            return wire_all_gather(
                p, axis_name, gather_dimension=dim, config=config
            ).astype(p.dtype)

        return jax.tree_util.tree_map(
            gather, dims, params, is_leaf=is_dim_leaf
        )

    mapped = jax.shard_map(
        body,
        mesh=partitioner.mesh,
        in_specs=(in_specs,),
        out_specs=jax.tree_util.tree_map(lambda _: P(), params),
        axis_names={axis_name},
        check_vma=False,  # a gathered leaf IS replicated; all_gather types it varying
    )
    return mapped(params)


# -- analytic wire accounting ----------------------------------------------


def _bytes_per_element(config: WireConfig, n: int) -> float:
    """Per-element payload bytes of ONE wire pass for an n-element leaf."""
    if config.compresses(n):
        # 1 s8 byte + one bf16 scale per block
        return 1.0 + 2.0 / config.block_size
    return 4.0  # f32


def grad_wire_report(params: Any, partitioner,
                     config: Optional[WireConfig] = None,
                     axis_name: str = "data") -> dict:
    """Analytic per-device gradient-sync wire bytes per optimizer step.

    Ring-algorithm accounting per param leaf of n elements over a
    D-shard axis: a reduce-scatter transmits ``(D-1)/D * n`` elements
    per device, an all-reduce (RS + AG) twice that. Scatterable leaves
    (the ZeRO-1 overlay dims) pay the RS factor; the rest pay the
    all-reduce factor. This is deliberately the PAYLOAD model, not the
    HLO result-buffer proxy ``analysis/collectives.py`` ratchets on —
    an int8 all-to-all's result buffer (n bytes) is LARGER than a tiled
    fp32 reduce-scatter's (n/D * 4), so result bytes cannot express the
    wire win; the budget entry records both, and the ``wire-int8-step``
    signature gates on this ratio plus the s8 payload's presence.
    """
    if config is None:
        config = getattr(partitioner, "wire", None) or WireConfig()
    d = int(partitioner.mesh.shape.get(axis_name, 1))
    if partitioner.dp_shard_opt_state:
        dims = partitioner.zero1_dims(params)
    else:
        dims = jax.tree_util.tree_map(lambda _: None, params)
    is_dim_leaf = lambda x: x is None  # noqa: E731 - tree of Optional[int]
    fp32_bytes = 0.0
    wire_bytes = 0.0
    ring_factor = (d - 1) / d if d > 1 else 0.0
    for dim, leaf in zip(
        jax.tree_util.tree_leaves(dims, is_leaf=is_dim_leaf),
        jax.tree_util.tree_leaves(params),
    ):
        n = int(getattr(leaf, "size", 0) or 0)
        passes = 1.0 if dim is not None else 2.0  # RS vs AR (= RS + AG)
        fp32_bytes += passes * ring_factor * n * 4.0
        wire_bytes += (
            passes * ring_factor * n * _bytes_per_element(config, n)
        )
    ratio = fp32_bytes / wire_bytes if wire_bytes else 1.0
    return {
        "compress": config.compress,
        "block_size": config.block_size,
        "dp_degree": d,
        "grad_wire_bytes_per_step_fp32": int(round(fp32_bytes)),
        "grad_wire_bytes_per_step": int(round(wire_bytes)),
        "wire_compression_ratio": round(ratio, 3),
    }
