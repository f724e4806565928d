"""Canonical wire schema — ONE packing program for every kernel tier.

Before this module, the repo had three independent spellings of "how halo
payloads are laid out on the wire": the XLA coalesced exchange's
ravel+concat pack (`ops.halo._exchange_dim_coalesced`), the quantized
pack/unpack pair (`_quant_pack_group`/`_quant_unpack_group`), and the
Pallas fused kernels' per-field in-kernel permutes (`pallas_wave`,
`pallas_stokes` — which therefore escaped PR 7's collective contracts and
PR 9's quantized wire entirely). TEMPI (arXiv:2012.14363) names the fix:
derive ONE canonical packing program from the datatype/slab spec and reuse
it everywhere.

`WireSchema` is that program. Built from the slab signature alone — slab
shapes x state dtype x exchange axis x `WireFormat` — it fixes:

- the **layout**: ``"slab"`` packs by concatenating the send slabs ALONG
  the exchange axis (slab shape preserved end-to-end: no ravel pass on
  pack, no reshape pass on unpack — the select/concat traffic that put the
  8-field coalesced exchange BELOW the per-field baseline on the CPU mesh,
  BENCH_ALL.json 0.75x); ``"flat"`` ravels and concatenates (required
  whenever the slab cross-shapes differ — staggered multi-field packs — or
  the payload is quantized, whose per-slab f32 scales ride a byte tail
  only a flat buffer has);
- the **wire dtype**: the state dtype, a narrower float cast, or int8
  bytes (bit-packed int4 included) per `precision.wire_format_for`;
- the **byte accounting**: ``payload_bytes`` is exact to the byte and is
  the single number `ops.halo._plan_from_sig`, `halo_comm_plan`,
  `telemetry.predict_step`, and `analysis.contracts` all price — the plan,
  the oracle, and the compiled-program audit can no longer drift apart on
  layout.

`pack(slabs)`/`unpack(buffer)` are the only two entry points; both tiers
call them: the XLA coalesced path packs Python-side slices, the Pallas
fused kernels pack the thin-slab mini-computes of
`ops.halo.exchange_recv_slabs_multi` — one ppermute pair per mesh axis per
round for EVERY tier, which is what lets `analysis.audit.audit_model`
derive real contracts for ``impl='pallas'`` programs.

On TPU grids the ``"slab"`` pack can additionally run as one fused Pallas
kernel (`pallas_halo.wire_pack_pallas` — all fields' slabs written into
the packed buffer in a single launch); `schema.pack` gates that on
`pallas_halo.wire_pack_supported` and falls back to the XLA concat
everywhere else (the CPU mesh measures the XLA slab layout directly).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from ..utils.exceptions import InvalidArgumentError
from .precision import (
    SCALE_BYTES, _AXIS_TOKENS, _DIM_NAMES, decode_scales, dequantize_slab,
    encode_scales, quant_slab_bytes, quantize_slab,
)

__all__ = ["WireSchema", "slab_schema", "schema_for_fields",
           "CommCadence", "resolve_comm_every"]


# ---------------------------------------------------------------------------
# per-axis exchange cadence (the comm_every knob's resolved form)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CommCadence:
    """Resolved PER-MESH-AXIS exchange cadence: one integer ``k >= 1`` per
    grid dimension (x, y, z) — the deep-halo ``comm_every`` knob
    generalized so each mesh axis pays its own collective latency at its
    own rate (the HiCCL per-link-class idea, arXiv:2408.05962, applied to
    the cadence axis the way `precision.WirePolicy` applies it to wire
    precision). Axis ``d`` exchanges once per ``k_d`` steps with
    ``depth * k_d``-wide slabs; ``k_d = 1`` is the exchange-every-step
    default. The canonical string form round-trips through
    `resolve_comm_every` (``"4"`` when uniform, else e.g. ``"z:4"`` —
    unnamed axes are cadence 1)."""

    per_dim: tuple

    def for_dim(self, dim: int) -> int:
        """Cadence along grid dimension ``dim`` (dims beyond the cadence
        — e.g. 2-D fields' missing z — exchange every step)."""
        if 0 <= int(dim) < len(self.per_dim):
            return self.per_dim[int(dim)]
        return 1

    @property
    def uniform(self):
        """The single cadence when every dim shares one, else ``None``."""
        ks = set(self.per_dim)
        return self.per_dim[0] if len(ks) == 1 else None

    @property
    def deep(self) -> bool:
        """Whether any axis runs a deep-halo cadence (``k > 1``)."""
        return any(k > 1 for k in self.per_dim)

    @property
    def cycle(self) -> int:
        """The super-cycle length: lcm of the per-axis cadences — after
        ``cycle`` sub-steps every axis has just exchanged, so the deep
        runners' compiled super-step advances exactly this many physical
        steps."""
        return math.lcm(*self.per_dim)

    def retreats(self, j: int, ndim: int = 3) -> tuple:
        """Per-dim staleness at sub-step ``j`` of a super-cycle: the
        number of sub-steps since the last exchange along each dim
        (``j mod k_d`` — exchanges land after sub-steps where
        ``(j+1) % k_d == 0``)."""
        return tuple(int(j) % self.for_dim(d) for d in range(ndim))

    def due_dims(self, j: int, ndim: int = 3, order=None) -> tuple:
        """Grid dims whose exchange is due after sub-step ``j``, in the
        exchange processing order (default z, x, y — the reference's
        sequential-corner order, `ops.halo.DEFAULT_DIMS_ORDER`)."""
        if order is None:
            from .halo import DEFAULT_DIMS_ORDER

            order = DEFAULT_DIMS_ORDER
        return tuple(d for d in order
                     if d < ndim and (int(j) + 1) % self.for_dim(d) == 0)

    def __str__(self) -> str:
        u = self.uniform
        if u is not None:
            return str(u)
        parts = [f"{_DIM_NAMES[d]}:{k}"
                 for d, k in enumerate(self.per_dim) if k != 1]
        return ",".join(parts) if parts else "1"

    def __repr__(self) -> str:
        return f"CommCadence({self})"


def _parse_cadence_k(token) -> int:
    try:
        k = int(str(token).strip())
    except (TypeError, ValueError):
        raise InvalidArgumentError(
            f"comm_every cadence must be an integer >= 1; got {token!r}.")
    if k < 1:
        raise InvalidArgumentError(
            f"comm_every cadence must be >= 1; got {k}.")
    return k


def resolve_comm_every(comm_every=None) -> CommCadence:
    """Resolve the requested exchange cadence to a `CommCadence`.

    ``comm_every=None`` consults ``IGG_COMM_EVERY``; an explicit argument
    wins over the environment. Accepted forms (the `resolve_wire_dtype`
    spelling family):

    - an integer ``k`` (or its string) — every axis exchanges once per
      ``k`` steps;
    - a per-axis spec ``"z:4,x:1"`` (axes ``x``/``y``/``z`` or
      ``gx``/``gy``/``gz``; unnamed axes stay cadence 1);
    - a ``{axis: k}`` mapping, or a `CommCadence`.

    The default — no argument, no environment — is the uniform cadence 1
    (exchange every step)."""
    import os

    if comm_every is None:
        comm_every = os.environ.get("IGG_COMM_EVERY")
    if comm_every is None or comm_every == "":
        return CommCadence((1, 1, 1))
    if isinstance(comm_every, CommCadence):
        return comm_every
    if isinstance(comm_every, dict):
        items = list(comm_every.items())
    elif isinstance(comm_every, str) and ":" in comm_every:
        items = []
        for part in comm_every.split(","):
            part = part.strip()
            if not part:
                continue
            if ":" not in part:
                raise InvalidArgumentError(
                    f"Per-axis comm_every spec {comm_every!r}: entry "
                    f"{part!r} must be '<axis>:<k>' (e.g. 'z:4,x:1').")
            axis, k = part.split(":", 1)
            items.append((axis, k))
    else:
        return CommCadence((_parse_cadence_k(comm_every),) * 3)

    per_dim = [1, 1, 1]
    seen = set()
    for axis, k in items:
        key = str(axis).strip().lower()
        dim = _AXIS_TOKENS.get(key)
        if dim is None:
            raise InvalidArgumentError(
                f"Unknown mesh axis {axis!r} in comm_every spec (use "
                "x/y/z or gx/gy/gz).")
        if dim in seen:
            raise InvalidArgumentError(
                f"Mesh axis {axis!r} named twice in comm_every spec.")
        seen.add(dim)
        per_dim[dim] = _parse_cadence_k(k)
    return CommCadence(tuple(per_dim))


@dataclass(frozen=True)
class WireSchema:
    """One direction's packing program for a group of same-dtype slabs.

    ``shapes`` are the send-slab shapes in pack order; ``dim`` the
    exchange array axis; ``fmt`` the resolved `WireFormat` (``None`` =
    exact wire); ``layout`` is ``"slab"`` or ``"flat"`` (see module
    docstring). Frozen and hashable — derived once per exchange signature
    and shared by the pack, the unpack, and every byte-accounting layer.

    ``members`` is the ENSEMBLE axis (ISSUE 12): an ensemble chunk
    advances E scenario members per step by ``vmap``-ing the member axis
    over the step program, and jax's collective batching rule turns each
    per-member ppermute into ONE ppermute whose payload carries every
    member's slabs — the same pair count, E x the bytes. The live
    `pack`/`unpack` therefore stay PER-MEMBER programs (the vmap batches
    them); ``members`` exists so the byte accounting (`payload_bytes`)
    prices the batched payload the compiler actually ships — including
    E x the per-slab scale tails of a quantized wire, one f32 scale per
    (member, slab) in the same scales-in-band layout.
    """

    dim: int
    shapes: tuple          # per-slab shapes, pack order
    state_dtype: str       # numpy dtype name
    fmt: object = None     # WireFormat | None
    layout: str = "slab"
    members: int = 1       # ensemble members riding one payload

    # -- derived geometry ---------------------------------------------------

    @property
    def n_slabs(self) -> int:
        return len(self.shapes)

    @property
    def cells(self) -> tuple:
        """Per-slab element counts, pack order."""
        return tuple(int(np.prod(s)) for s in self.shapes)

    @property
    def is_quant(self) -> bool:
        return self.fmt is not None and self.fmt.is_quant

    @property
    def wire_dtype(self):
        """The numpy dtype the packed buffer crosses the link in."""
        if self.fmt is not None:
            return np.dtype(self.fmt.dtype)
        return np.dtype(self.state_dtype)

    @property
    def payload_bytes(self) -> int:
        """EXACT bytes of one direction's packed payload — the number every
        wire-reasoning layer prices (`halo_comm_plan` by-dtype rows,
        `predict_step` per-axis pricing, `exchange_contract` wire-byte
        equality against the compiled program). With ``members`` > 1 the
        per-member payload (quantized slabs + their per-slab scales
        included) multiplies by the member count — the vmap-batched
        buffer one ppermute carries."""
        if self.is_quant:
            per_member = (sum(quant_slab_bytes(c, self.fmt)
                              for c in self.cells)
                          + SCALE_BYTES * self.n_slabs)
        else:
            per_member = sum(self.cells) * int(self.wire_dtype.itemsize)
        return per_member * max(1, int(self.members))

    @property
    def wire_key(self) -> str:
        """The `halo_comm_plan` ``by_dtype`` key of this payload (the
        format name for quantized wire, the dtype name otherwise)."""
        return self.fmt.name if self.is_quant else str(self.wire_dtype)

    # -- the packing program ------------------------------------------------

    def pack(self, slabs, *, pallas_mode=None):
        """Pack the per-field send slabs into ONE wire buffer.

        ``slabs`` are arrays of exactly ``self.shapes`` (pack order).
        ``pallas_mode`` is ``None`` (XLA pack) or ``(use_kernel,
        interpret)`` from `pallas_halo.wire_pack_mode` — the fused
        single-launch pack of the slab layout on TPU grids."""
        import jax.numpy as jnp

        self._check(slabs)
        if self.is_quant:
            qs, scales = zip(*(quantize_slab(s.reshape(-1), self.fmt)
                               for s in slabs))
            return jnp.concatenate(list(qs) + [encode_scales(list(scales))])
        if self.layout == "flat":
            buf = jnp.concatenate([s.reshape(-1) for s in slabs])
        elif pallas_mode is not None and pallas_mode[0]:
            from .pallas_halo import wire_pack_pallas

            buf = wire_pack_pallas(list(slabs), dim=self.dim,
                                   interpret=pallas_mode[1])
        elif len(slabs) == 1:
            buf = slabs[0]
        else:
            buf = jnp.concatenate(list(slabs), axis=self.dim)
        if self.fmt is not None:
            buf = buf.astype(self.wire_dtype)
        return buf

    def unpack(self, buf):
        """Inverse of `pack`: the received wire buffer back into per-field
        slabs of ``self.shapes`` in the state dtype (dequantized /
        upcast — boundary masking and delivery stay with the caller)."""
        import jax.numpy as jnp
        from jax import lax

        out_dt = np.dtype(self.state_dtype)
        if self.is_quant:
            cells = self.cells
            qsizes = [quant_slab_bytes(c, self.fmt) for c in cells]
            data = sum(qsizes)
            scales = decode_scales(
                lax.slice_in_dim(buf, data,
                                 data + SCALE_BYTES * self.n_slabs, axis=0),
                self.n_slabs)
            out, off = [], 0
            for k, (c, qb) in enumerate(zip(cells, qsizes)):
                flat = dequantize_slab(
                    lax.slice_in_dim(buf, off, off + qb, axis=0),
                    scales[k], c, self.fmt, out_dt)
                out.append(flat.reshape(self.shapes[k]))
                off += qb
            return out
        if self.fmt is not None:
            buf = buf.astype(out_dt)
        out = []
        if self.layout == "flat":
            off = 0
            for shp, c in zip(self.shapes, self.cells):
                out.append(lax.slice_in_dim(buf, off, off + c,
                                            axis=0).reshape(shp))
                off += c
            return out
        if self.n_slabs == 1:
            return [buf]
        off = 0
        for shp in self.shapes:
            w = int(shp[self.dim])
            out.append(lax.slice_in_dim(buf, off, off + w, axis=self.dim))
            off += w
        return out

    def _check(self, slabs) -> None:
        if len(slabs) != self.n_slabs:
            raise InvalidArgumentError(
                f"WireSchema.pack: {len(slabs)} slabs for a "
                f"{self.n_slabs}-slab schema.")
        for s, shp in zip(slabs, self.shapes):
            if tuple(int(v) for v in s.shape) != shp:
                raise InvalidArgumentError(
                    f"WireSchema.pack: slab shape {tuple(s.shape)} does "
                    f"not match the schema's {shp}.")


def _slab_layout_ok(dim: int, shapes) -> bool:
    """Whether the slab (concat-along-axis) layout applies: every slab must
    share the cross-axis extents (staggered multi-field packs differ there
    and take the flat layout)."""
    cross = None
    for shp in shapes:
        c = tuple(v for d, v in enumerate(shp) if d != dim)
        if cross is None:
            cross = c
        elif c != cross:
            return False
    return True


def slab_schema(dim: int, shapes, state_dtype, fmt=None,
                members: int = 1) -> WireSchema:
    """Derive the canonical schema for one (axis, dtype group) from the
    slab signature alone. ``fmt`` is the resolved `WireFormat` for this
    axis (`precision.wire_format_for`), or ``None`` for exact wire;
    ``members`` is the ensemble member count riding the payload (byte
    accounting only — the live pack stays per-member under vmap)."""
    shapes = tuple(tuple(int(v) for v in s) for s in shapes)
    if not shapes:
        raise InvalidArgumentError("slab_schema needs at least one slab.")
    if int(members) < 1:
        raise InvalidArgumentError(
            f"slab_schema: members must be >= 1; got {members}.")
    quant = fmt is not None and fmt.is_quant
    layout = "flat" if quant or not _slab_layout_ok(dim, shapes) else "slab"
    return WireSchema(dim=int(dim), shapes=shapes,
                      state_dtype=str(np.dtype(state_dtype)), fmt=fmt,
                      layout=layout, members=int(members))


def schema_for_fields(dim: int, shapes, hws, state_dtype,
                      fmt=None, members: int = 1) -> WireSchema:
    """`slab_schema` from FIELD shapes (local blocks) instead of slab
    shapes: the send slab of a field along ``dim`` is its cross extents x
    the halowidth. The one geometry rule (`ops.halo`: slab width = hw)
    lives here so the static plan and the live pack can never disagree."""
    slab_shapes = []
    for shp, hw in zip(shapes, hws):
        s = list(int(v) for v in shp)
        s[dim] = int(hw)
        slab_shapes.append(tuple(s))
    return slab_schema(dim, slab_shapes, state_dtype, fmt, members=members)
