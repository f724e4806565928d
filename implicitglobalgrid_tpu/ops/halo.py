"""Halo exchange — the hot path.

TPU-native re-design of the reference's `update_halo!`
(`/root/reference/src/update_halo.jl:29-83`). The reference's machinery per
dimension — pack kernels into send buffers (`update_halo.jl:212-269`,
`CUDAExt/update_halo.jl:210-227`), nonblocking `MPI.Isend`/`MPI.Irecv!`
(`update_halo.jl:337-361`), unpack, and a buffer pool (`update_halo.jl:97-201`)
— collapses on TPU into ONE pair of `lax.ppermute` collectives per (axis,
direction) inside `shard_map`:

    slice send slab  →  ppermute over the mesh axis (ICI hop)  →
    dynamic_update_slice into the halo region

XLA fuses the slicing around the collective, owns all buffers, and its
latency-hiding scheduler overlaps the permutes of independent fields — the
roles of the reference's pinned staging buffers, max-priority CUDA streams
(`CUDAExt/update_halo.jl:157`), and multi-field pipelining (`update_halo.jl:17`).

Exchange semantics reproduced exactly (index math from
`update_halo.jl:275-296`, 0-based here):

- send slab, right side (n=2): ``[s-ol, s-ol+hw)``; left (n=1): ``[ol-hw, ol)``
- recv slab, right side (n=2): ``[s-hw, s)``;      left (n=1): ``[0, hw)``
- a field participates along a dim iff ``ol(dim, A) >= 2*hw[dim]``
  (`update_halo.jl:233`)
- dimensions are processed strictly sequentially (default order z, x, y —
  `update_halo.jl:29,45`) so corner/edge values propagate across dims; the
  data dependence through the updated array enforces this under XLA too.
- non-periodic boundary shards keep their halo values (the reference's
  `MPI.PROC_NULL` no-op neighbors, `init_global_grid.jl:103`): masked with a
  select on the mesh coordinate (`lax.axis_index`).
- a periodic axis with a single shard short-circuits to local slab copies
  (the reference's self-neighbor path, `update_halo.jl:62-68,363-380`).

Collective coalescing (default ON; `IGG_HALO_COALESCE=0` or ``coalesce=False``
reverts): when several fields of one dtype exchange along a ppermute axis,
their send slabs pack into ONE buffer per direction on the CANONICAL WIRE
SCHEMA (`ops.wire.WireSchema` — slab layout: concat along the exchange
axis, slab shape preserved end-to-end; flat layout for staggered
cross-shapes and quantized payloads), so the axis costs a single ppermute
pair REGARDLESS of field count — the latency-bound cost of N small
collectives collapses into one message per link (the aggregation result
of HiCCL, arXiv:2408.05962; the reference's analog is its multi-field
pipelining note, `update_halo.jl:17`). The SAME schema drives the fused
Pallas kernels' exchange (`exchange_recv_slabs_multi`) and every
byte-accounting layer (`halo_comm_plan` -> `predict_step` ->
`exchange_contract`). Unpacking splits the receive buffer back into
per-field slabs and delivers them via the multi-field Pallas kernel
(`pallas_halo.halo_write_multi_pallas`, one launch per axis) or per-field
`dynamic_update_slice`; on TPU grids the pack side can likewise run as
one fused launch (`pallas_halo.wire_pack_pallas`). Fields that cannot
ride a packed exchange (lone dtype on an axis, non-participating dims)
fall back to the per-field path; self-neighbor axes have no collective to
coalesce and keep their local copies. Results are bit-identical to the
per-field path (tests/test_update_halo.py) — packing is pure layout, no
arithmetic.

Wire precision (default OFF; `IGG_HALO_WIRE_DTYPE` / ``wire_dtype=``): float
state optionally crosses the link narrowed (the EQuARX play,
arXiv:2506.17615) — either as a narrower float CAST
(convert → pack → ppermute → unpack → convert back, ~2x) or QUANTIZED as
symmetric per-slab-scaled ``int8`` / bit-packed ``int4`` (quantize each
field's send slab against its own max-abs scale, append the f32 scales to
the coalesced flat buffer, ppermute ONE int8 payload per direction,
dequantize on unpack — ~3.5-7.5x less wire traffic). The policy is PER
MESH AXIS (``wire_dtype="z:int8,x:f32"``): a slow DCN-mapped axis can
quantize while ICI axes stay exact (HiCCL, arXiv:2408.05962). Applies to
every ppermute payload (coalesced or per-field; quantized fields always
ride the packed layout, whose flat buffer carries the scales); PROC_NULL
boundary halos and self-neighbor local copies never round-trip through
the wire format. See `ops.precision.wire_format_for`.
"""

from __future__ import annotations

import contextlib

import numpy as np

from ..parallel.topology import (
    AXIS_NAMES, NDIMS, check_initialized, global_grid, grid_epoch,
)
from ..utils.exceptions import IncoherentArgumentError, InvalidArgumentError
from .fields import (
    Field, check_fields, extract, field_partition_spec, wrap_field,
)
from .precision import resolve_wire_dtype, wire_format_for
from .wire import schema_for_fields, slab_schema

__all__ = ["update_halo", "local_update_halo", "free_update_halo_caches",
           "halo_may_use_pallas", "resolve_halo_coalesce", "halo_comm_plan",
           "exchange_recv_slabs", "exchange_recv_slabs_multi",
           "force_xla_exchange", "DEFAULT_DIMS_ORDER"]

# Reference default `dims=(3,1,2)` (1-based: z, x, y — update_halo.jl:29).
DEFAULT_DIMS_ORDER = (2, 0, 1)

# jit-compiled exchange functions keyed by (grid epoch, field signature, dims
# order). The analog of the reference's persistent buffer pool + task/stream
# pools (`update_halo.jl:97-201,207`): allocated lazily on first use, reused
# across calls, freed by `finalize_global_grid`.
_exchange_cache: dict = {}

# Static wire plans keyed like the exchange cache (telemetry comm
# accounting: computed once per signature, charged per call).
_plan_cache: dict = {}


def free_update_halo_caches() -> None:
    """Drop compiled exchange programs (analog of
    `free_update_halo_buffers`, reference `update_halo.jl:103-108`).
    Epochs RETAINED by the multi-run scheduler survive (one tenant's
    finalize — e.g. inside an elastic restart — must not cold-start the
    other tenants' exchanges); with nothing retained this is the full
    clear it always was."""
    from ..parallel.topology import _retained_epochs

    for cache in (_exchange_cache, _plan_cache):
        for k in [k for k in cache if k[0] not in _retained_epochs]:
            del cache[k]


def halo_may_use_pallas(gg=None) -> bool:
    """Whether `local_update_halo` may emit Pallas kernels on the current
    grid (in-place halo writes / single-pass self-exchange).

    Enclosing `shard_map`s must pass ``check_vma=False`` when this is True —
    Pallas outputs cannot express the mesh-axis variance the checker wants.
    Model runners consult this instead of assuming from the device type, so
    the variance check stays on for genuinely pure-XLA programs (e.g.
    ``IGG_USE_PALLAS=0`` on a TPU grid)."""
    if gg is None:
        check_initialized()
        gg = global_grid()
    return _FORCE_PALLAS_WRITE_INTERPRET or (
        gg.device_type == "tpu" and bool(gg.use_pallas.any())
    )


def _normalize_dims_order(dims):
    if dims is None:
        return DEFAULT_DIMS_ORDER
    out = tuple(int(d) for d in (dims if np.iterable(dims) else (dims,)))
    if any(d < 0 or d >= NDIMS for d in out):
        raise InvalidArgumentError(
            f"dims must contain 0-based dimension indices in [0, {NDIMS}); got {out}. "
            "(Note: this API is 0-based; the Julia reference's default (3,1,2) is (2,0,1) here.)"
        )
    return out


def resolve_halo_coalesce(coalesce=None) -> bool:
    """Whether multi-field exchanges pack one ppermute pair per (axis, dtype
    group). An explicit argument wins; else ``IGG_HALO_COALESCE`` (default
    ON)."""
    if coalesce is not None:
        return bool(coalesce)
    import os

    v = os.environ.get("IGG_HALO_COALESCE")
    if v is None:
        return True
    try:
        return int(v) > 0
    except ValueError as e:
        raise InvalidArgumentError(
            f"Environment variable IGG_HALO_COALESCE: expected an integer, "
            f"got {v!r}.") from e


def _dim_meta(gg, dim: int):
    """Static per-dimension exchange metadata."""
    D = int(gg.dims[dim])
    periodic = bool(gg.periods[dim])
    disp = int(gg.disp)
    return D, periodic, disp


# Test hook: force the in-place Pallas halo-write kernels in interpret mode
# (CPU) so the kernel path is exercised by the emulated-mesh test suite.
_FORCE_PALLAS_WRITE_INTERPRET = False

# Trace-scoped kernel-tier override: the ensemble runner pins its vmapped
# step to the pure-XLA exchange (every XLA op has a vmap batching rule;
# the Pallas halo kernels' batching is unvalidated hardware territory).
_FORCE_XLA_TIER = False


@contextlib.contextmanager
def force_xla_exchange():
    """Context manager pinning `local_update_halo` to the pure-XLA tier
    (no Pallas halo kernels) for the duration of a TRACE. Used by
    `models.common.make_state_runner(ensemble=...)` around its vmapped
    step: the exchange's slices/permutes/updates all batch by jax rule,
    while a Pallas kernel launched under vmap would lean on `pallas_call`
    batching this repo has never validated on hardware. The flag is
    consulted at trace time by every kernel-tier gate below."""
    global _FORCE_XLA_TIER
    prev = _FORCE_XLA_TIER
    _FORCE_XLA_TIER = True
    try:
        yield
    finally:
        _FORCE_XLA_TIER = prev


def _pallas_write_mode(gg, dim, shape, hw):
    """(use_kernel, interpret) for the halo unpack along ``dim``."""
    from .pallas_halo import halo_write_supported

    if _FORCE_XLA_TIER or not halo_write_supported(shape, dim, hw):
        return False, False
    if _FORCE_PALLAS_WRITE_INTERPRET:
        return True, True
    return bool(gg.use_pallas[dim]) and gg.device_type == "tpu", False


def _pallas_tier_enabled(gg, shape, dims_order) -> bool:
    """Shared gate for the whole-exchange Pallas kernels (self-exchange and
    combined one-pass): default order, 3-D, TPU with all per-dim flags on
    (the kernels cover every dim at once), or the test force flag."""
    if _FORCE_XLA_TIER:
        return False
    if tuple(dims_order) != DEFAULT_DIMS_ORDER or len(shape) != 3:
        return False
    return _FORCE_PALLAS_WRITE_INTERPRET or (
        bool(gg.use_pallas.all()) and gg.device_type == "tpu")


def _self_exchange_plan(gg, shape, hws, dims_order):
    """If every participating dim of a field with this local ``shape`` takes
    the self-neighbor path, return (modes, ols) for the single-pass kernel
    (`pallas_halo.halo_self_exchange_pallas`); else None.

    Only valid when ALL exchanging dims are self-neighbor: a mix would break
    the reference's strict dim sequencing (a later self dim must see an
    earlier ppermute dim's received corners). The kernel hardwires the
    default z, x, y order.
    """
    from .pallas_halo import self_exchange_supported

    if not _pallas_tier_enabled(gg, shape, dims_order):
        return None
    modes = [False, False, False]
    ols = [0, 0, 0]
    for dim in range(3):
        D = int(gg.dims[dim])
        periodic = bool(gg.periods[dim])
        hw = int(hws[dim])
        ol_d = int(gg.overlaps[dim] + (shape[dim] - gg.nxyz[dim]))
        if D == 1 and not periodic:
            continue                      # no exchange
        if ol_d < 2 * hw:
            continue                      # computation-overlap only
        if D != 1 or not periodic or int(gg.disp) != 1:
            return None                   # a ppermute dim: no single-pass
        modes[dim] = True
        ols[dim] = ol_d
    if not self_exchange_supported(shape, modes, hws):
        return None
    return tuple(modes), tuple(ols)


def _dim_exchanges(gg, shape, hws, dim) -> bool:
    """Whether a field of this local ``shape`` exchanges along ``dim`` (the
    participation gates of the per-dim loop)."""
    if dim >= len(shape):
        return False
    D, periodic, disp = _dim_meta(gg, dim)
    if D == 1 and not periodic:
        return False
    if D > 1 and not periodic and disp >= D:
        return False  # Cart_shift beyond the grid: all-PROC_NULL, no-op
    ol_d = int(gg.overlaps[dim] + (shape[dim] - gg.nxyz[dim]))
    return ol_d >= 2 * int(hws[dim])


def _combined_plan(gg, shape, hws, dims_order):
    """Participation modes for the combined one-pass exchange
    (`pallas_halo.halo_write_combined_pallas`), or None if inapplicable.

    Used when dim 2 exchanges with at least one ppermute dim in play (the
    all-self case goes to the cheaper `halo_self_exchange_pallas`): dim 2's
    lane-edge halo forces array-level traffic no matter what, so delivering
    ALL dims' slabs in one full pass beats one array rewrite per dim.
    """
    from .pallas_halo import combined_write_supported

    if not _pallas_tier_enabled(gg, shape, dims_order):
        return None
    modes = tuple(_dim_exchanges(gg, shape, hws, dim) for dim in range(3))
    if not combined_write_supported(shape, modes, hws):
        return None
    return modes


def exchange_recv_slabs_multi(gg, shapes, hws, modes, getters, *,
                              wire=None, coalesce=None):
    """Masked, corner-patched RECEIVED slabs for every (field, dim) — the
    shared slab pipeline of every fused kernel tier, on the CANONICAL wire
    schema: per dim, all participating fields' send slabs pack into ONE
    buffer per direction (`ops.wire.slab_schema`) and the axis costs a
    single ppermute pair per (axis, dtype group) REGARDLESS of field count
    — the same wire the XLA coalesced tier ships, which is what lets the
    collective contracts and the quantized wire cover the Pallas programs
    (`analysis.audit.audit_model(impl='pallas')`).

    Per dim, in the reference's write order (z, x, y — `update_halo.jl:29`):
    extract each field's send slabs via its ``getters[f](dim, start,
    size)`` hook (a plain slice for a standalone exchange, a freshly
    COMPUTED slab when a model fuses its update with the exchange), patch
    them with THAT field's earlier received values (slab-level corner
    propagation — exactly equivalent to the sequential per-dim writes),
    pack + permute (or swap locally for self-neighbor dims), unpack, and
    mask non-periodic boundaries per field with the patched current halos
    (the PROC_NULL no-op, `init_global_grid.jl:103`).

    ``shapes``/``modes``/``getters`` are dicts keyed by field name (the
    dict order is the pack order); ``hws`` is the shared per-dim halowidth
    tuple. ``wire`` is the RESOLVED wire policy (or None = exact);
    ``coalesce=None`` resolves `resolve_halo_coalesce` (OFF restores one
    pair per field). Returns ``{field: {dim: (recv_l, recv_r)}}``.
    """
    import jax.numpy as jnp
    from jax import lax

    if coalesce is None:
        coalesce = resolve_halo_coalesce(None)
    names = list(getters)
    earlier = {f: [] for f in names}  # [(dim, hw, (recv_l, recv_r))]
    recvs = {f: {} for f in names}

    def patch(f, slab, d, start, size):
        """Apply field ``f``'s earlier dims' received halo values to a slab
        spanning [start, start+size) along d (full extent elsewhere)."""
        for e, hw_e, (rl, rr) in earlier[f]:
            rl_s = lax.slice_in_dim(rl, start, start + size, axis=d)
            rr_s = lax.slice_in_dim(rr, start, start + size, axis=d)
            slab = lax.dynamic_update_slice_in_dim(slab, rl_s, 0, axis=e)
            slab = lax.dynamic_update_slice_in_dim(
                slab, rr_s, slab.shape[e] - hw_e, axis=e)
        return slab

    for dim in DEFAULT_DIMS_ORDER:
        parts = [f for f in names if modes[f][dim]]
        if not parts:
            continue
        D, periodic, disp = _dim_meta(gg, dim)
        hw = int(hws[dim])
        sends = {}
        for f in parts:
            s = shapes[f][dim]
            ol_d = int(gg.overlaps[dim] + (shapes[f][dim] - gg.nxyz[dim]))
            send_r = patch(f, getters[f](dim, s - ol_d, hw), dim,
                           s - ol_d, hw)
            send_l = patch(f, getters[f](dim, ol_d - hw, hw), dim,
                           ol_d - hw, hw)
            sends[f] = (send_l, send_r)
        if D == 1:  # periodic self-neighbor: local swap, no wire
            for f in parts:
                send_l, send_r = sends[f]
                recvs[f][dim] = (send_r, send_l)
                earlier[f].append((dim, hw, recvs[f][dim]))
            continue
        perm_p, perm_m = _perm_pairs(D, periodic, disp)
        axis_name = AXIS_NAMES[dim]
        by_dt = {}
        for f in parts:
            by_dt.setdefault(np.dtype(sends[f][0].dtype), []).append(f)
        for dt, fs in by_dt.items():
            fmt = wire_format_for(dt, wire, dim)
            groups = [fs] if coalesce else [[f] for f in fs]
            for g in groups:
                schema = slab_schema(
                    dim, [sends[f][0].shape for f in g], dt, fmt)
                buf_r = schema.pack([sends[f][1] for f in g])
                buf_l = schema.pack([sends[f][0] for f in g])
                rls = schema.unpack(lax.ppermute(buf_r, axis_name, perm_p))
                rrs = schema.unpack(lax.ppermute(buf_l, axis_name, perm_m))
                if not periodic:  # PROC_NULL edges keep current halos EXACT
                    idx = lax.axis_index(axis_name)
                    for k, f in enumerate(g):
                        s = shapes[f][dim]
                        cur_l = patch(f, getters[f](dim, 0, hw), dim, 0, hw)
                        cur_r = patch(f, getters[f](dim, s - hw, hw), dim,
                                      s - hw, hw)
                        rls[k] = jnp.where(idx >= disp, rls[k], cur_l)
                        rrs[k] = jnp.where(idx < D - disp, rrs[k], cur_r)
                for k, f in enumerate(g):
                    recvs[f][dim] = (rls[k], rrs[k])
        for f in parts:
            earlier[f].append((dim, hw, recvs[f][dim]))
    return recvs


def exchange_recv_slabs(gg, shape, hws, modes, get_slab, *, wire=None):
    """Single-field form of `exchange_recv_slabs_multi` (the combined
    one-pass exchange and the single-field fused kernels). Returns
    ``{dim: (recv_l, recv_r)}``."""
    return exchange_recv_slabs_multi(
        gg, {"A": shape}, hws, {"A": modes}, {"A": get_slab},
        wire=wire)["A"]


def _combined_exchange(gg, a, hws, modes, interpret):
    """All-dims exchange with ONE unpack pass: the `exchange_recv_slabs`
    pipeline on plain slices, then `halo_write_combined_pallas` writes every
    received slab in a single full-array pass."""
    from jax import lax

    from .pallas_halo import halo_write_combined_pallas

    recvs = exchange_recv_slabs(
        gg, a.shape, hws, modes,
        lambda dim, start, size: lax.slice_in_dim(a, start, start + size,
                                                  axis=dim))
    return halo_write_combined_pallas(a, recvs, modes=modes, hws=hws,
                                      interpret=interpret)


def _apply_self_exchange(gg, arrays, hws, dims_order):
    """Run the single-pass self-neighbor kernel on every eligible field.
    Mutates ``arrays``; returns ``handled`` flags (True = fully exchanged)."""
    handled = [False] * len(arrays)
    for i, a in enumerate(arrays):
        plan = _self_exchange_plan(gg, a.shape, hws[i], dims_order)
        if plan is not None:
            from .pallas_halo import halo_self_exchange_pallas

            arrays[i] = halo_self_exchange_pallas(
                a, modes=plan[0], ols=plan[1],
                interpret=_FORCE_PALLAS_WRITE_INTERPRET,
            )
            handled[i] = True
    return handled


def _perm_pairs(D, periodic, disp):
    """The (forward, backward) ppermute pairs of an exchanging axis —
    wrap-around when periodic, truncated chains (PROC_NULL edges) when not.
    Delegates to `parallel.topology.axis_perm_pairs`: ONE pair generator
    shared by the per-field path, the coalesced path, and the contracts,
    so the wire pattern can never diverge between layers."""
    from ..parallel.topology import axis_perm_pairs

    return axis_perm_pairs(D, periodic, disp)


def _check_slab_fit(s, dim, ol_d, hw):
    if not (0 <= s - ol_d and ol_d - hw >= 0 and hw <= s):
        raise IncoherentArgumentError(
            f"Field of local size {s} along dimension {dim} cannot hold send slabs "
            f"(overlap {ol_d}, halowidth {hw})."
        )


def _coalesce_groups(gg, arrays, hws, handled, dims_order, coalesce=True,
                     wire=None):
    """Packing plan for the coalesced exchange: ``{dim: [group, ...]}``
    where each group is a tuple of field indices of ONE dtype that all
    exchange along ppermute axis ``dim``. Without wire quantization a
    group needs >= 2 fields (a lone field per dtype gains nothing from
    packing and keeps the per-field path — the fallback the packer
    declares by simply not grouping). A dtype the policy QUANTIZES along
    ``dim`` always rides the packed path — its payload carries the
    appended per-slab scales, a layout only the flat buffer has — even as
    a singleton, and with ``coalesce=False`` each quantized field packs
    its own buffer (per-field collective count preserved)."""
    out = {}
    for dim in dims_order:
        D, periodic, disp = _dim_meta(gg, dim)
        if D == 1:
            continue  # self-neighbor / no-neighbor axes: nothing to pack
        by_dt = {}
        for i, a in enumerate(arrays):
            if handled[i]:
                continue
            if _dim_exchanges(gg, a.shape, hws[i], dim):
                by_dt.setdefault(np.dtype(a.dtype), []).append(i)
        groups = []
        for dt, idxs in by_dt.items():
            fmt = wire_format_for(dt, wire, dim)
            quant = fmt is not None and fmt.is_quant
            if quant and not coalesce:
                groups.extend((i,) for i in idxs)
            elif quant or (coalesce and len(idxs) >= 2):
                groups.append(tuple(idxs))
        if groups:
            out[dim] = groups
    return out


def _coalesced_pallas_mode(gg, dim, shapes, hws_dim):
    """(use_multi_kernel, interpret) for the coalesced unpack along
    ``dim`` — the multi-field analog of `_pallas_write_mode`."""
    from .pallas_halo import multi_write_supported

    if _FORCE_XLA_TIER or not multi_write_supported(shapes, dim, hws_dim):
        return False, False
    if _FORCE_PALLAS_WRITE_INTERPRET:
        return True, True
    return bool(gg.use_pallas[dim]) and gg.device_type == "tpu", False


def _wire_pack_mode(gg, dim, shapes, hws_dim, schema):
    """``(use_kernel, interpret)`` for the fused Pallas PACK of a
    slab-layout wire buffer (one launch writes every field's send slab
    into the packed payload — `pallas_halo.wire_pack_pallas`), or ``None``
    for the XLA concat pack. Gated on the same conditions as the
    multi-field unpack kernel (so `_build_exchange_fn`'s check_vma
    accounting holds) plus `pallas_halo.wire_pack_supported`; quantized
    payloads always pack through the flat XLA program (their scale-tail
    arithmetic is elementwise work XLA already fuses well)."""
    from .pallas_halo import wire_pack_supported

    if schema.layout != "slab" or schema.is_quant:
        return None
    use, interp = _coalesced_pallas_mode(gg, dim, shapes, hws_dim)
    # budget with the STATE dtype: the kernel packs the raw slabs and any
    # cast wire narrowing happens after (`WireSchema.pack`)
    if not use or not wire_pack_supported(schema.shapes, dim,
                                          schema.state_dtype):
        return None
    return True, interp


def _exchange_dim_coalesced(gg, arrays, idxs, hws, dim, wire=None):
    """Exchange the halos of fields ``idxs`` (one dtype) along ``dim`` with
    ONE ppermute pair, on the canonical wire schema (`ops.wire`): pack
    every field's send slab into one buffer per direction, permute,
    unpack, deliver. Mutates ``arrays``. With exact wire, values are
    bit-identical to the per-field exchange — the pack stage is pure
    layout (slab layout: one concat along the exchange axis, no
    ravel/reshape passes; the PROC_NULL boundary select runs per-field on
    slab-sized operands). Under a cast wire format the buffer crosses the
    link narrowed; under a QUANT format (int8/int4) each field's slab is
    quantized against its own max-abs scale and the f32 scales ride the
    same flat buffer — still one ppermute pair, wire bytes ~4-8x down."""
    import jax.numpy as jnp
    from jax import lax

    D, periodic, disp = _dim_meta(gg, dim)
    axis_name = AXIS_NAMES[dim]
    perm_p, perm_m = _perm_pairs(D, periodic, disp)

    metas = []  # (i, hw, s, slab_shape)
    sends_r, sends_l, curs_l, curs_r = [], [], [], []
    for i in idxs:
        a = arrays[i]
        hw = int(hws[i][dim])
        s = a.shape[dim]
        ol_d = int(gg.overlaps[dim] + (s - gg.nxyz[dim]))
        _check_slab_fit(s, dim, ol_d, hw)
        send_r = lax.slice_in_dim(a, s - ol_d, s - ol_d + hw, axis=dim)
        send_l = lax.slice_in_dim(a, ol_d - hw, ol_d, axis=dim)
        metas.append((i, hw, s, send_r.shape))
        sends_r.append(send_r)
        sends_l.append(send_l)
        if not periodic:  # exact-precision boundary halos (PROC_NULL no-op)
            curs_l.append(lax.slice_in_dim(a, 0, hw, axis=dim))
            curs_r.append(lax.slice_in_dim(a, s - hw, s, axis=dim))

    state_dt = arrays[idxs[0]].dtype
    fmt = wire_format_for(state_dt, wire, dim)
    schema = slab_schema(dim, [m[3] for m in metas], state_dt, fmt)
    pk = _wire_pack_mode(gg, dim, [arrays[i].shape for i in idxs],
                         [m[1] for m in metas], schema)
    recv_l = schema.unpack(lax.ppermute(
        schema.pack(sends_r, pallas_mode=pk), axis_name, perm_p))
    recv_r = schema.unpack(lax.ppermute(
        schema.pack(sends_l, pallas_mode=pk), axis_name, perm_m))
    if not periodic:  # per-field slab-sized selects (no cur-parts concat)
        idxv = lax.axis_index(axis_name)
        recv_l = [jnp.where(idxv >= disp, rl, cur)
                  for rl, cur in zip(recv_l, curs_l)]
        recv_r = [jnp.where(idxv < D - disp, rr, cur)
                  for rr, cur in zip(recv_r, curs_r)]
    slab_pairs = list(zip(recv_l, recv_r))  # aligned with metas

    use_multi, interp = _coalesced_pallas_mode(
        gg, dim, [arrays[i].shape for i in idxs], [m[1] for m in metas])
    if use_multi:
        from .pallas_halo import halo_write_multi_pallas

        outs = halo_write_multi_pallas(
            [arrays[i] for i in idxs], slab_pairs,
            dim=dim, hw=metas[0][1], interpret=interp)
        for i, o in zip(idxs, outs):
            arrays[i] = o
        return
    for (i, hw, s, _), (rl, rr) in zip(metas, slab_pairs):
        pw, interp = _pallas_write_mode(gg, dim, arrays[i].shape, hw)
        if pw:
            from .pallas_halo import halo_write_inplace

            arrays[i] = halo_write_inplace(arrays[i], rl, rr, dim=dim, hw=hw,
                                           interpret=interp)
        else:
            a = lax.dynamic_update_slice_in_dim(arrays[i], rl, 0, axis=dim)
            arrays[i] = lax.dynamic_update_slice_in_dim(a, rr, s - hw,
                                                        axis=dim)


def _exchange_arrays(gg, arrays, hws, dims_order, coalesce=None, wire=None):
    """Exchange every field's halos (local view; inside shard_map).
    Mutates and returns ``arrays``. Kernel-path selection per field:
    all-self single-pass kernel > coalesced packed exchange (multi-field
    dtype groups) > combined one-pass unpack > per-dim per-field.

    ``coalesce=None`` resolves `resolve_halo_coalesce` (env default ON);
    ``wire`` is the RESOLVED wire policy (`precision.resolve_wire_dtype`)
    or None for full-precision wire. Wire mode routes its fields through
    the coalesced/per-dim paths (the combined one-pass tier has its own
    full-precision permutes); quantized formats always ride the packed
    path (the scales live in the flat buffer — `_coalesce_groups`)."""
    if coalesce is None:
        coalesce = resolve_halo_coalesce(None)
    handled = _apply_self_exchange(gg, arrays, hws, dims_order)
    groups_by_dim = _coalesce_groups(gg, arrays, hws, handled, dims_order,
                                     coalesce=coalesce, wire=wire)
    grouped = {i for gs in groups_by_dim.values() for g in gs for i in g}
    def wire_touches(a, hw):
        # whether the policy can actually reach one of THIS field's
        # ppermute payloads: a policy-named dim that is unpartitioned
        # (D==1: self-copies stay exact) or that the field does not
        # exchange along is a no-op for it
        return any(
            wire_format_for(a.dtype, wire, d) is not None
            and _dim_meta(gg, d)[0] > 1
            and _dim_exchanges(gg, a.shape, hw, d)
            for d in dims_order)

    for i, a in enumerate(arrays):
        # wire-affected fields skip the combined tier (its permutes are
        # full-precision); fields the wire policy can never touch (ints,
        # already-narrow floats, fields whose policy-named dims carry no
        # ppermute for them) keep the faster one-pass kernel — evicting
        # those would pay per-dim exchanges for bit-identical results.
        if handled[i] or i in grouped or wire_touches(a, hws[i]):
            continue
        modes = _combined_plan(gg, a.shape, hws[i], dims_order)
        if modes is not None:
            arrays[i] = _combined_exchange(
                gg, a, hws[i], modes, _FORCE_PALLAS_WRITE_INTERPRET)
            handled[i] = True
    for dim in dims_order:
        D, periodic, disp = _dim_meta(gg, dim)
        if D == 1 and not periodic:
            continue  # no neighbors along this axis (reference update_halo.jl:45 note)
        in_group = set()
        for g in groups_by_dim.get(dim, ()):
            in_group.update(g)
            _exchange_dim_coalesced(gg, arrays, list(g), hws, dim, wire)
        for i, a in enumerate(arrays):
            if handled[i] or i in in_group or dim >= a.ndim:
                continue
            hw = int(hws[i][dim])
            ol_d = int(gg.overlaps[dim] + (a.shape[dim] - gg.nxyz[dim]))
            if ol_d < 2 * hw:
                continue  # computation overlap only, no halo (update_halo.jl:233)
            pw, interp = _pallas_write_mode(gg, dim, a.shape, hw)
            arrays[i] = _exchange_dim_local(
                a, dim=dim, hw=hw, ol_d=ol_d, D=D, periodic=periodic,
                disp=disp, axis_name=AXIS_NAMES[dim],
                pallas_write=pw, interpret=interp, wire=wire,
            )
    return arrays


def _exchange_dim_local(a, *, dim, hw, ol_d, D, periodic, disp, axis_name,
                        pallas_write=False, interpret=False, wire=None):
    """Exchange the halos of local block ``a`` along array axis ``dim``.

    Runs inside `shard_map`. All shapes/indices are static; only the mesh
    coordinate (`axis_index`) is traced. With ``pallas_write``, the unpack
    writes the halo slabs in place via the Pallas kernels (`pallas_halo.py`)
    instead of full-array `dynamic_update_slice` rewrites. ``wire`` is the
    resolved wire policy: CAST formats narrow the ppermute payloads here
    (`precision.wire_format_for`); QUANT formats never reach this path —
    `_coalesce_groups` routes every quantized field through the packed
    exchange, whose flat buffer carries the per-slab scales. Local
    self-neighbor copies and PROC_NULL boundary halos stay exact.
    """
    import jax.numpy as jnp
    from jax import lax

    s = a.shape[dim]
    _check_slab_fit(s, dim, ol_d, hw)

    def write_halos(a, into_l, into_r):
        """Halo writes: left halo <- ``into_l``, right halo <- ``into_r``."""
        if pallas_write:
            from .pallas_halo import halo_write_inplace

            return halo_write_inplace(a, into_l, into_r, dim=dim, hw=hw,
                                      interpret=interpret)
        a = lax.dynamic_update_slice_in_dim(a, into_l, 0, axis=dim)
        a = lax.dynamic_update_slice_in_dim(a, into_r, s - hw, axis=dim)
        return a

    # Send slabs (reference sendranges, update_halo.jl:275-284).
    send_r = lax.slice_in_dim(a, s - ol_d, s - ol_d + hw, axis=dim)   # n=2
    send_l = lax.slice_in_dim(a, ol_d - hw, ol_d, axis=dim)           # n=1

    if D == 1:
        if not periodic:
            return a
        # Self-neighbor: periodic axis with one shard — pure local copies
        # (reference sendrecv_halo_local, update_halo.jl:363-380):
        # left halo <- own right slab, right halo <- own left slab.
        return write_halos(a, send_r, send_l)

    perm_p, perm_m = _perm_pairs(D, periodic, disp)
    if not perm_p and not perm_m:
        return a

    fmt = wire_format_for(a.dtype, wire, dim)
    wire_dt = None if fmt is None or fmt.is_quant else fmt.dtype
    if wire_dt is not None:
        send_r = send_r.astype(wire_dt)
        send_l = send_l.astype(wire_dt)

    # Both directions posted before any consumption — the analog of the
    # reference posting all Irecv!/Isend before waiting (update_halo.jl:51-60);
    # XLA schedules the two collectives concurrently.
    recv_l = lax.ppermute(send_r, axis_name, perm_p) if perm_p else None  # from coord-disp
    recv_r = lax.ppermute(send_l, axis_name, perm_m) if perm_m else None  # from coord+disp
    if wire_dt is not None:
        recv_l = recv_l.astype(a.dtype) if recv_l is not None else None
        recv_r = recv_r.astype(a.dtype) if recv_r is not None else None

    idx = lax.axis_index(axis_name)
    if not periodic:  # PROC_NULL edges: boundary shards keep their halos
        cur_l = lax.slice_in_dim(a, 0, hw, axis=dim)
        recv_l = jnp.where(idx >= disp, recv_l, cur_l)
        cur_r = lax.slice_in_dim(a, s - hw, s, axis=dim)
        recv_r = jnp.where(idx < D - disp, recv_r, cur_r)
    return write_halos(a, recv_l, recv_r)


def local_update_halo(*fields, dims=None, coalesce=None, wire_dtype=None):
    """Halo-exchange local blocks — use INSIDE `shard_map` over the grid mesh.

    This is the local-view programming model of the reference (user code runs
    per rank; `update_halo!(A)` in the hot loop, e.g.
    `examples/diffusion3D_multicpu_novis.jl:47`): call it inside your own
    `shard_map`-mapped step function on per-shard blocks. Functional: returns
    the updated array(s).

    Arguments may be arrays or ``Field(A, halowidths)``; ``dims`` is the
    0-based dimension processing order (default z, x, y like the reference's
    `(3,1,2)`). ``coalesce`` packs multi-field exchanges into one ppermute
    pair per (axis, dtype group) — default from ``IGG_HALO_COALESCE`` (ON);
    ``wire_dtype`` ships float payloads across the link narrowed (float
    casts) or per-slab-scale quantized (``int8``/``int4``), optionally per
    mesh axis (``"z:int8,x:f32"``) — default from ``IGG_HALO_WIRE_DTYPE``
    (OFF); see the module docstring.

    NOTE: on a default TPU grid this emits Pallas kernels (in-place halo
    writes / single-pass self-exchange), which cannot pass `shard_map`'s
    variance checker — build your enclosing `shard_map` with
    ``check_vma=not halo_may_use_pallas()`` (the model runners in
    `models/common.py` do this automatically).
    """
    check_initialized()
    gg = global_grid()
    dims_order = _normalize_dims_order(dims)
    fs = [wrap_field(f) for f in fields]
    arrays = _exchange_arrays(gg, [f.A for f in fs],
                              [f.halowidths for f in fs], dims_order,
                              coalesce=resolve_halo_coalesce(coalesce),
                              wire=resolve_wire_dtype(wire_dtype))
    return arrays[0] if len(arrays) == 1 else tuple(arrays)


def _build_exchange_fn(gg, sig, dims_order, coalesce, wire):
    """Compile the jitted shard_map exchange program for a field signature.
    ``coalesce`` and ``wire`` are pre-resolved (`update_halo`)."""
    import jax

    from jax import shard_map

    ndims_arr = [len(shape) for (shape, _, _) in sig]
    in_specs = tuple(field_partition_spec(nd) for nd in ndims_arr)
    hws = [hw for (_, _, hw) in sig]

    # Pallas kernels under shard_map require check_vma=False (their outputs
    # can't express the mesh-axis variance the checker wants — same rule as
    # the model step kernels, models/diffusion.py). The per-field plans are
    # a superset of the coalesced path's kernel gates (`multi_write_supported`
    # is strictly tighter than per-field `halo_write_supported`), so this
    # stays correct when coalescing reroutes fields.
    any_pallas = any(
        _self_exchange_plan(gg, shape, hw, dims_order) is not None
        or _combined_plan(gg, shape, hw, dims_order) is not None
        or any(
            _dim_exchanges(gg, shape, hw, dim)
            and _pallas_write_mode(gg, dim, shape, int(hw[dim]))[0]
            for dim in dims_order
        )
        for (shape, _, hw) in sig
    )

    def exchange(*locals_):
        return tuple(_exchange_arrays(gg, list(locals_), hws, dims_order,
                                      coalesce=coalesce, wire=wire))

    shmapped = shard_map(
        exchange, mesh=gg.mesh, in_specs=in_specs, out_specs=in_specs,
        check_vma=not any_pallas,
    )
    return jax.jit(shmapped)


class _SigField:
    """Shape/dtype stand-in for a field signature entry, so the routing
    helpers (`_coalesce_groups`, `_dim_exchanges`) serve the static wire
    plan without real arrays."""

    __slots__ = ("shape", "dtype", "ndim")

    def __init__(self, shape, dtype):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self.ndim = len(self.shape)


def _plan_from_sig(gg, sig, dims_order, coalesce, wire,
                   ensemble=None) -> dict:
    """Static comm accounting for one exchange signature: collective
    counts and bytes-on-wire derived purely from shapes/overlaps/wire
    dtype — no tracing, no device work (the TPU analog of the reference's
    printed GB/s estimate, computed instead of measured).

    The wire pattern is invariant across kernel tiers (Pallas unpack,
    combined one-pass, plain `dynamic_update_slice` all consume the SAME
    permuted slabs), so the plan only branches on what actually changes
    the wire: coalescing (one packed ppermute pair per (axis, dtype
    group) instead of one pair per field) and the wire policy (narrowed
    or quantized payloads — a quantized group's bytes count the int8/
    packed-int4 slabs PLUS the `SCALE_BYTES` f32 scale per slab, exactly
    the buffer `WireSchema.pack` ships, so the plan stays exact to the
    byte). ``wire_bytes`` sums the payload over every source->dest
    link of the permute (all shards), both directions;
    ``local_copy_bytes`` counts self-neighbor slab swaps that never touch
    the interconnect.

    ``ensemble`` prices the ENSEMBLE axis (ISSUE 12): an E-member chunk
    vmaps the member axis over the step, so jax's collective batching
    keeps the ppermute COUNT identical while every payload (and every
    self-neighbor local copy) carries E members' slabs — bytes x E,
    launches flat in E. The schema's ``members`` field is the single
    byte source, so quantized payloads price E x the per-(member, slab)
    scale tails exactly as `WireSchema.payload_bytes` ships them."""
    E = 1
    if ensemble is not None:
        E = int(ensemble)
        if E < 1:
            # loud, like every runner-side layer: a silently clamped plan
            # would hand a tuner valid-looking solo numbers for a
            # configuration the runtime rejects
            raise InvalidArgumentError(
                f"halo_comm_plan: ensemble must be >= 1; got {ensemble}.")
    fields = [_SigField(shape, dt) for (shape, dt, _) in sig]
    hws = [tuple(int(h) for h in hw) for (_, _, hw) in sig]

    def slab_cells(i, dim):
        shp = fields[i].shape
        return int(np.prod(shp)) // shp[dim] * hws[i][dim]

    axes: dict = {}

    def axis_rec(dim):
        return axes.setdefault(
            AXIS_NAMES[dim], {"ppermutes": 0, "wire_bytes": 0,
                              "by_dtype": {}})

    def add_wire(dim, payload_bytes, key, npairs):
        rec = axis_rec(dim)
        rec["ppermutes"] += 2
        b = payload_bytes * npairs
        rec["wire_bytes"] += b
        rec["by_dtype"][key] = rec["by_dtype"].get(key, 0) + b

    local_bytes = 0
    # per-AXIS split of the self-neighbor copy traffic: a per-axis
    # comm_every cadence amortizes each axis's local swaps at that axis's
    # own rate, so the oracle needs the split, not just the total
    local_by_axis: dict = {}
    groups_by_dim = _coalesce_groups(
        gg, fields, hws, [False] * len(fields), dims_order,
        coalesce=coalesce, wire=wire)
    for dim in dims_order:
        D, periodic, disp = _dim_meta(gg, dim)
        if D == 1 and not periodic:
            continue
        perm_p, perm_m = _perm_pairs(D, periodic, disp)
        npairs = len(perm_p) + len(perm_m)
        in_group = set()
        for g in groups_by_dim.get(dim, ()):  # groups only form on D>1 axes
            in_group.update(g)
            f0 = fields[g[0]]
            fmt = wire_format_for(f0.dtype, wire, dim)
            # ONE pricing source for every packed payload: the canonical
            # schema the live exchange ships (`ops.wire`) — exact to the
            # byte incl. quantized slabs + their `SCALE_BYTES` scale tail
            schema = schema_for_fields(
                dim, [fields[i].shape for i in g],
                [hws[i][dim] for i in g], f0.dtype, fmt, members=E)
            add_wire(dim, schema.payload_bytes, schema.wire_key, npairs)
        for i, f in enumerate(fields):
            if i in in_group or not _dim_exchanges(gg, f.shape, hws[i], dim):
                continue
            if D == 1:  # periodic self-neighbor: local slab swap, no wire
                b = 2 * slab_cells(i, dim) * f.dtype.itemsize * E
                local_bytes += b
                local_by_axis[AXIS_NAMES[dim]] = (
                    local_by_axis.get(AXIS_NAMES[dim], 0) + b)
                continue
            fmt = wire_format_for(f.dtype, wire, dim)
            wd = np.dtype(fmt.dtype if fmt is not None else f.dtype)
            add_wire(dim, slab_cells(i, dim) * wd.itemsize * E, str(wd),
                     npairs)
    return {
        "fields": len(fields),
        "coalesce": bool(coalesce),
        "wire_dtype": None if wire is None else str(wire),
        "ensemble": E,
        "axes": axes,
        "ppermutes": sum(r["ppermutes"] for r in axes.values()),
        "wire_bytes": sum(r["wire_bytes"] for r in axes.values()),
        "local_copy_bytes": local_bytes,
        "local_copy_by_axis": local_by_axis,
    }


def _normalized_fields(fields):
    """`update_halo`'s argument normalization: ``(A, hw)`` tuples ->
    `Field`, pytrees exploded (reference `update_halo.jl:31-32`), ndim
    and per-field coherence validated."""
    fs = []
    for f in fields:
        if isinstance(f, tuple) and not isinstance(f, Field) and len(f) == 2 \
                and hasattr(f[0], "shape") and not hasattr(f[1], "shape"):
            fs.append(wrap_field(f[0], f[1]))
        else:
            fs.extend(wrap_field(x) for x in extract(f))
    if not fs:
        raise InvalidArgumentError("update_halo requires at least one field.")
    for f in fs:
        if not hasattr(f.A, "shape"):
            raise InvalidArgumentError("update_halo requires array inputs.")
        if not (1 <= f.A.ndim <= NDIMS):
            raise InvalidArgumentError(
                f"update_halo supports 1-D to {NDIMS}-D arrays; got {f.A.ndim}-D."
            )
    check_fields(fs)
    return fs


def _stacked_sig(gg, fs) -> tuple:
    """The exchange signature of normalized fields: LOCAL shapes (stacked
    sizes divided by ``dims`` — validated even), dtype strings, halowidths.

    Dtypes are CANONICALIZED the way ``jnp.asarray`` will canonicalize the
    arrays (x64-disabled jax demotes f64 -> f32), so the signature — and
    everything keyed on it: the compiled-exchange cache, the wire plan —
    always describes the arrays actually exchanged."""
    import jax

    for f in fs:
        for d in range(f.A.ndim):
            if int(f.A.shape[d]) % int(gg.dims[d]) != 0:
                raise IncoherentArgumentError(
                    f"Global (stacked) array size {f.A.shape[d]} along dimension {d} is not "
                    f"divisible by dims[{d}]={int(gg.dims[d])}. update_halo operates on "
                    "stacked global arrays (dims * local size); see local_update_halo for "
                    "the local view."
                )
    return tuple(
        (
            tuple(int(s) // int(gg.dims[d]) for d, s in enumerate(f.A.shape)),
            str(jax.dtypes.canonicalize_dtype(np.dtype(f.A.dtype))),
            tuple(int(h) for h in f.halowidths),
        )
        for f in fs
    )


def halo_comm_plan(*fields, dims=None, coalesce=None, wire_dtype=None,
                   ensemble=None) -> dict:
    """Static bytes-on-wire / collective-count plan for an `update_halo`
    call with these stacked fields — derived from shapes, overlaps, and
    the wire dtype alone; nothing is compiled or dispatched (zero device
    syncs). Fields accept the same forms as `update_halo` (arrays,
    `Field`, ``(A, hw)`` tuples, pytrees) and anything with
    ``shape``/``dtype`` (e.g. `jax.ShapeDtypeStruct`) works.

    ``ensemble=E`` prices the exchange inside an E-member ensemble chunk
    (`models.common.make_state_runner(ensemble=E)`): the PHYSICAL field
    shapes stay what you pass here (no member axis — the plan describes
    one member's geometry) while every payload multiplies by E behind
    the SAME ppermute pairs (jax's collective batching under vmap;
    ``ppermutes`` is flat in E by construction).

    Returns ``{fields, coalesce, wire_dtype, ensemble,
    axes: {axis: {ppermutes, wire_bytes, by_dtype}},
    ppermutes, wire_bytes, local_copy_bytes, local_copy_by_axis}``.
    `update_halo` charges exactly this plan to the telemetry registry
    (``igg_halo_*`` counters) on every call."""
    check_initialized()
    gg = global_grid()
    dims_order = _normalize_dims_order(dims)
    fs = _normalized_fields(fields)
    sig = _stacked_sig(gg, fs)
    return _plan_from_sig(gg, sig, dims_order,
                          resolve_halo_coalesce(coalesce),
                          resolve_wire_dtype(wire_dtype),
                          ensemble=ensemble)


def update_halo(*fields, dims=None, coalesce=None, wire_dtype=None):
    """Update the halo of the given global (stacked) array(s).

    Controller-side API of the reference's `update_halo!`
    (`/root/reference/src/update_halo.jl:29-36`): arrays are stacked/global
    `jax.Array`s (shape ``dims * local_shape``, sharded over the grid mesh —
    each shard is one reference rank-local array). JAX arrays are immutable, so
    the call is FUNCTIONAL and returns the updated array(s)::

        T = update_halo(T)
        A, B, C = update_halo(A, B, (C, (2, 2, 2)))   # per-field halowidths

    Fields may be arrays, ``Field(A, halowidths)``, ``(A, halowidths)`` tuples,
    or pytrees of arrays (the CellArray analog, reference `shared.jl:133-137`).
    Group several fields in one call for best performance — same-dtype fields
    COALESCE into one ppermute pair per mesh axis (``coalesce``, default from
    ``IGG_HALO_COALESCE``: ON), the stronger form of the reference's
    multi-field pipelining note (`update_halo.jl:17-18`). ``wire_dtype``
    (default from ``IGG_HALO_WIRE_DTYPE``: OFF) ships float payloads across
    the link at reduced precision — float casts or per-slab-scaled
    ``int8``/``int4`` quantization, per mesh axis (``"z:int8,x:f32"``);
    see the module docstring.

    Example (doctest):

    >>> import numpy as np
    >>> import implicitglobalgrid_tpu as igg
    >>> _ = igg.init_global_grid(4, 4, 4, dimx=2, dimy=2, dimz=2,
    ...                          periodx=1, quiet=True)
    >>> T = igg.ones_g(dtype=np.float32)    # stacked (8, 8, 8)
    >>> T = igg.update_halo(T)
    >>> tuple(T.shape)
    (8, 8, 8)
    >>> igg.finalize_global_grid()
    """
    import jax.numpy as jnp

    check_initialized()
    gg = global_grid()
    dims_order = _normalize_dims_order(dims)

    # Normalize (tuples (A, hw) → Field; pytrees exploded, reference :31-32)
    # and validate the stacked layout: every sharded dim must divide evenly.
    fs = _normalized_fields(fields)
    arrays = [jnp.asarray(f.A) for f in fs]
    # Signature uses LOCAL shapes: the exchange math runs on per-shard blocks.
    sig = _stacked_sig(gg, fs)
    coalesce_r = resolve_halo_coalesce(coalesce)
    wire_r = resolve_wire_dtype(wire_dtype)
    key = (grid_epoch(), sig, dims_order, _FORCE_PALLAS_WRITE_INTERPRET,
           coalesce_r, str(wire_r))
    fn = _exchange_cache.get(key)
    if fn is None:
        fn = _build_exchange_fn(gg, sig, dims_order, coalesce_r, wire_r)
        _exchange_cache[key] = fn
    # Static comm accounting: charge the signature's wire plan per call
    # (computed once per signature, pure host arithmetic — no syncs).
    plan = _plan_cache.get(key)
    if plan is None:
        plan = _plan_from_sig(gg, sig, dims_order, coalesce_r, wire_r)
        _plan_cache[key] = plan
    from ..telemetry import account_halo_exchange

    account_halo_exchange(plan)
    out = fn(*arrays)
    return out[0] if len(out) == 1 else tuple(out)
