"""Pallas async bidirectional-ring collectives for TPU.

XLA already emits ring collectives, but it schedules them as opaque
fusion barriers: the reduce-scatter for microbatch k cannot overlap the
backward compute of microbatch k+1 inside the ``grad_accum_steps`` scan
(train/step.py). These kernels rebuild all-gather and reduce-scatter out
of explicit inter-chip DMAs (``pltpu.make_async_remote_copy`` — the
SNIPPETS.md [1] / pallas-guide right-permute idiom) so the data movement
is ordinary async copies the Mosaic scheduler can interleave with
surrounding compute:

- **Bidirectional ring**: the local payload splits in half; the low half
  travels clockwise (to ``me+1``), the high half counter-clockwise, so
  BOTH ICI directions carry bytes every hop and per-link traffic halves
  versus a unidirectional ring at the same (D-1)/D * n total.
- **Double buffering**: two semaphore/accumulator slots per direction,
  alternating by hop, so hop h+1's DMA issues while hop h's completion
  is still outstanding on the other slot — the wait for the next chunk
  runs behind the reduce-add of the current one. This is the compute
  overlap the wire layer buys inside the grad-accum scan.

These kernels only lower on the TPU backend: ``ring_supported()`` gates
every caller, and the 8-device virtual CPU mesh the tests run on always
takes the XLA collective with identical numerics. The TPU compiler's
acceptance of both kernels is pinned without a chip by
tests/test_chip_compile.py (AOT for a described v5e:2x2); their numerics
on four real chips by ``chip_smoke.py --chips 4``.

Scope notes:

- Int8 payloads (the wire-compressed gather halves, parallel/wire.py)
  ride the ring fine — gathering moves bytes without arithmetic. The
  quantized REDUCE cannot: int8 partial sums overflow and every hop
  would need a requantize, so the compressed reduce-scatter stays on the
  XLA all-to-all decomposition (see parallel/wire.py).
- Neighbor addressing is by mesh coordinate along ``axis_name`` only
  (``DeviceIdType.MESH`` with a ``{axis_name: index}`` dict: every other
  mesh axis keeps this device's own coordinate), so the ring runs inside
  whatever multi-axis mesh the partitioner built. A payload shape the
  kernels do not cover takes the XLA collective;
  ``WireConfig(ring="off")`` is the unconditional escape hatch.
- The reduce-scatter walks its payload in row tiles (``_RS_TILE_ROWS``)
  on a sequential grid, one full ring pass per tile, so VMEM use is
  bounded by the tile and not by the bucket (a bucket holding GPT-2's
  embedding is 154 MB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ...runtime.mesh import free_mesh_axes

_LANES = 128  # VREG lane width: work buffers are shaped (rows, 128)
# reduce-scatter row tile: (D, 2, 512, 128) f32 in + two (2, 2, 512, 128)
# work buffers is ~7 MiB at D=4 with the pipeline's double buffering —
# inside the 16 MiB scoped-VMEM default of a v5e
_RS_TILE_ROWS = 512


def ring_supported() -> bool:
    """True when the async ring kernels can lower on this backend."""
    return jax.default_backend() == "tpu" and len(jax.devices()) > 1


def _half_rows(n: int):
    """Rows of the (rows, 128) half-payload buffer, or None if the local
    payload cannot split into two lane-aligned halves."""
    if n and n % (2 * _LANES) == 0:
        return n // 2 // _LANES
    return None


def _fully_manual(kernel):
    """``kernel`` under a shard_map over the mesh axes that are not manual
    yet (operands replicated over them): the wire layer calls these
    kernels from regions manual over the gradient-sync axis ONLY, and the
    TPU lowering refuses a Mosaic kernel anywhere short of fully manual."""
    mesh, free = free_mesh_axes()
    if not free:
        return kernel
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=P(), out_specs=P(),
        axis_names=set(free), check_vma=False,
    )


def _neighbors(axis_name: str, num_devices: int):
    """(my index, right id, left id) along ``axis_name``; ids are MESH
    dicts, so the other mesh axes keep this device's coordinates."""
    me = lax.axis_index(axis_name)
    right = lax.rem(me + 1, num_devices)
    left = lax.rem(me - 1 + num_devices, num_devices)
    return me, {axis_name: right}, {axis_name: left}


def _neighbor_barrier(right, left):
    """Local barrier with both neighbors: nobody DMAs into a peer that
    has not entered the kernel yet (pallas guide, RDMA section)."""
    barrier = pltpu.get_barrier_semaphore()
    for peer in (right, left):
        pltpu.semaphore_signal(
            barrier, device_id=peer,
            device_id_type=pltpu.DeviceIdType.MESH,
        )
    pltpu.semaphore_wait(barrier, 2)


# -- all-gather -------------------------------------------------------------


def _ag_kernel(x_ref, out_ref, send_sems, recv_sems, *, axis_name,
               num_devices):
    """Bidirectional ring all-gather body.

    ``x_ref``: (2, rows, 128) — the local shard's two direction-halves.
    ``out_ref``: (D, 2, rows, 128) — slot d collects device d's shard.
    Each device seeds its own slot, then on hop h forwards the chunk
    that arrived h hops back: clockwise the low half of chunk (me - h),
    counter-clockwise the high half of chunk (me + h). After D-1 hops
    every slot is full. Semaphore slots alternate by hop (double
    buffer); the two directions' DMAs are both in flight before either
    is waited on, keeping both ICI directions busy.
    """
    me, right, left = _neighbors(axis_name, num_devices)
    _neighbor_barrier(right, left)

    # seed my own slot with my shard
    seed = pltpu.make_async_copy(x_ref, out_ref.at[me], recv_sems.at[0, 0])
    seed.start()
    seed.wait()

    for h in range(num_devices - 1):
        slot = h % 2
        c_cw = lax.rem(me - h + num_devices, num_devices)
        c_ccw = lax.rem(me + h, num_devices)
        cw = pltpu.make_async_remote_copy(
            src_ref=out_ref.at[c_cw, 0],
            dst_ref=out_ref.at[c_cw, 0],
            send_sem=send_sems.at[0, slot],
            recv_sem=recv_sems.at[0, slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        ccw = pltpu.make_async_remote_copy(
            src_ref=out_ref.at[c_ccw, 1],
            dst_ref=out_ref.at[c_ccw, 1],
            send_sem=send_sems.at[1, slot],
            recv_sem=recv_sems.at[1, slot],
            device_id=left,
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        cw.start()
        ccw.start()  # both directions in flight before either wait
        cw.wait()
        ccw.wait()


def ring_all_gather(x, axis_name: str, *, stream: int = 0):
    """Tiled axis-0 all-gather along ``axis_name`` via the async
    bidirectional ring — the drop-in shape contract of
    ``lax.all_gather(x, axis_name, axis=0, tiled=True)``. Call inside a
    shard_map manual over ``axis_name``; any backend or payload shape
    the kernel does not cover takes the identical-numerics XLA path.
    The dispatch boundary carries a ``ring_all_gather`` named scope, so
    a device trace attributes the moved bytes to this kernel.

    ``stream`` selects an independent collective buffer set: concurrent
    ring kernels in one program (the per-bucket gathers of the overlap
    path, parallel/wire.py sync_grads) MUST carry distinct streams —
    ``collective_id`` keys the cross-device barrier-semaphore match-up
    (pallas guide, RDMA section), so two in-flight kernels sharing an id
    would handshake with each other's barriers. Gathers take the even
    ids (``2 * stream``), reduce-scatters the odd.
    """
    with jax.named_scope("ring_all_gather"):
        return _ring_all_gather(x, axis_name, stream)


def _ring_all_gather(x, axis_name: str, stream: int = 0):
    d = lax.axis_size(axis_name)
    rows = _half_rows(x.size)
    if d == 1 or rows is None or not ring_supported():
        return lax.all_gather(x, axis_name, axis=0, tiled=True)
    return _fully_manual(
        lambda x: all_gather_kernel(x, axis_name, d, rows, stream)
    )(x)


def all_gather_kernel(x, axis_name: str, d: int, rows: int, stream: int = 0,
                      interpret=False):
    """The ring all-gather ``pallas_call`` itself (no backend dispatch —
    tests/test_chip_compile.py compiles this for a described TPU, and
    tests/test_wire.py runs it on the CPU mesh under the Pallas TPU
    interpreter, ``interpret=pltpu.InterpretParams()``)."""
    halves = x.reshape(2, rows, _LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((2, 2)),  # send: [direction, slot]
            pltpu.SemaphoreType.DMA((2, 2)),  # recv
        ],
    )
    stacked = pl.pallas_call(
        functools.partial(
            _ag_kernel, axis_name=axis_name, num_devices=d
        ),
        out_shape=jax.ShapeDtypeStruct((d,) + halves.shape, x.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            collective_id=2 * int(stream), has_side_effects=True,
        ),
        interpret=interpret,
    )(halves)
    return stacked.reshape((d * x.shape[0],) + x.shape[1:])


# -- reduce-scatter ---------------------------------------------------------


def _rs_kernel(parts_ref, out_ref, acc_ref, recv_ref, send_sems,
               recv_sems, *, axis_name, num_devices):
    """Bidirectional ring reduce-scatter body, one row tile per grid step.

    ``parts_ref``: (D, 2, tile, 128) f32, destination-major — chunk d is
    bound for device d, split into two direction-halves. Classic ring
    RS run twice at half payload: clockwise the partial for chunk
    (me - 1 - h) departs at hop h and each receiver folds in its own
    contribution, so after D-1 hops device me holds the full sum of its
    own chunk's low half; counter-clockwise mirrors for the high half.
    ``acc_ref``/``recv_ref`` are (2, 2, tile, 128) VMEM [direction,
    slot]: the hop-g DMA lands in slot g%2 while the reduce-add that
    prepares hop g+1 writes slot (g+1)%2 — the double buffer that lets
    the adds overlap the in-flight DMAs. ``g`` counts hops ACROSS grid
    steps, so a slot is always reused two hops after its last use; each
    hop waits on both neighbors' sends, which a neighbor only issues
    after folding the hop before, so by then the slot has been read.
    """
    step = pl.program_id(0)
    me, right, left = _neighbors(axis_name, num_devices)

    @pl.when(step == 0)
    def _enter():
        _neighbor_barrier(right, left)

    g0 = step * (num_devices - 1)
    # seed: the first chunk each stream pushes is the pure local partial
    s0 = lax.rem(g0, 2)
    acc_ref[0, s0] = parts_ref[lax.rem(me - 1 + num_devices, num_devices), 0]
    acc_ref[1, s0] = parts_ref[lax.rem(me + 1, num_devices), 1]

    for h in range(num_devices - 1):
        slot = lax.rem(g0 + h, 2)
        nxt = 1 - slot
        cw = pltpu.make_async_remote_copy(
            src_ref=acc_ref.at[0, slot],
            dst_ref=recv_ref.at[0, slot],
            send_sem=send_sems.at[0, slot],
            recv_sem=recv_sems.at[0, slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        ccw = pltpu.make_async_remote_copy(
            src_ref=acc_ref.at[1, slot],
            dst_ref=recv_ref.at[1, slot],
            send_sem=send_sems.at[1, slot],
            recv_sem=recv_sems.at[1, slot],
            device_id=left,
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        cw.start()
        ccw.start()
        cw.wait()
        ccw.wait()
        # fold my contribution into the just-received partials; on the
        # final hop the received chunk IS mine, so this add completes it
        c_cw = lax.rem(me - 2 - h + 2 * num_devices, num_devices)
        c_ccw = lax.rem(me + 2 + h, num_devices)
        if h == num_devices - 2:
            out_ref[0] = recv_ref[0, slot] + parts_ref[c_cw, 0]
            out_ref[1] = recv_ref[1, slot] + parts_ref[c_ccw, 1]
        else:
            acc_ref[0, nxt] = recv_ref[0, slot] + parts_ref[c_cw, 0]
            acc_ref[1, nxt] = recv_ref[1, slot] + parts_ref[c_ccw, 1]


def ring_reduce_scatter(x, axis_name: str, *, scatter_dimension: int = 0,
                        stream: int = 0):
    """Tiled reduce-scatter via the async bidirectional ring — the
    drop-in contract of ``lax.psum_scatter(..., tiled=True)``, f32
    accumulation. Falls back to the XLA collective off-TPU and for any
    payload the kernel does not cover (chunk not splittable into two
    lane-aligned halves). Dispatch carries a ``ring_reduce_scatter``
    named scope for graft-lens overlap attribution.

    ``stream`` selects an independent collective buffer set (odd
    ``collective_id`` = ``2 * stream + 1``) so the per-bucket fused
    reduce-scatters of the overlap path can be in flight concurrently —
    see :func:`ring_all_gather` for the barrier-semaphore rationale.
    """
    with jax.named_scope("ring_reduce_scatter"):
        return _ring_reduce_scatter(x, axis_name, scatter_dimension, stream)


def _ring_reduce_scatter(x, axis_name: str, scatter_dimension: int = 0,
                         stream: int = 0):
    d = lax.axis_size(axis_name)
    if (
        d == 1
        or not ring_supported()
        or x.shape[scatter_dimension] % d
    ):
        return lax.psum_scatter(
            x, axis_name, scatter_dimension=scatter_dimension, tiled=True
        )
    return _fully_manual(
        lambda x: reduce_scatter_kernel(
            x, axis_name, d, scatter_dimension, stream
        )
    )(x)


def reduce_scatter_kernel(x, axis_name: str, d: int,
                          scatter_dimension: int = 0, stream: int = 0,
                          interpret=False):
    """The ring reduce-scatter ``pallas_call`` itself (no backend
    dispatch; compiled and interpreted by the same tests as
    :func:`all_gather_kernel`). Each destination chunk is zero-padded to
    whole row tiles."""
    dim = scatter_dimension
    chunk = x.shape[dim] // d
    parts = jnp.moveaxis(
        x.reshape(x.shape[:dim] + (d, chunk) + x.shape[dim + 1:]), dim, 0
    )
    chunk_shape = parts.shape[1:]
    n = 1
    for s in chunk_shape:
        n *= int(s)
    # rows of one direction-half, rounded up to the (8, 128) f32 tile and,
    # past one row tile, to whole tiles
    rows = -(-n // (2 * _LANES))
    tile = min(_RS_TILE_ROWS, -(-rows // 8) * 8)
    rows = -(-rows // tile) * tile
    flat = parts.astype(jnp.float32).reshape(d, n)
    pad = 2 * rows * _LANES - n
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    halves = flat.reshape(d, 2, rows, _LANES)
    work = (2, 2, tile, _LANES)  # [direction, slot] double buffers
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(rows // tile,),
        in_specs=[
            pl.BlockSpec((d, 2, tile, _LANES), lambda i: (0, 0, i, 0)),
        ],
        out_specs=pl.BlockSpec((2, tile, _LANES), lambda i: (0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM(work, jnp.float32),     # acc
            pltpu.VMEM(work, jnp.float32),     # recv
            pltpu.SemaphoreType.DMA((2, 2)),   # send
            pltpu.SemaphoreType.DMA((2, 2)),   # recv
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _rs_kernel, axis_name=axis_name, num_devices=d
        ),
        out_shape=jax.ShapeDtypeStruct((2, rows, _LANES), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            collective_id=2 * int(stream) + 1, has_side_effects=True,
        ),
        interpret=interpret,
    )(halves)
    return out.reshape(-1)[:n].reshape(chunk_shape).astype(x.dtype)
