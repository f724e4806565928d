"""The compiled redistribution program: plan rounds -> ppermute slices.

`reshard_state` is the HBM-to-HBM fast path of an elastic ``dims``
change: it re-blocks the LIVE state onto a new decomposition with no
disk round-trip. Mechanics, in order:

1. The plan (`reshard.plan.build_reshard_plan`) is derived from the live
   grid's topology and the state's shapes — host arithmetic only.
2. The grid is re-initialized onto the destination dims (same implicit
   global grid; `elastic_local_size` math), exactly like
   `runtime.recovery.elastic_restart` — but the state never leaves HBM.
3. A FLAT one-axis mesh (axis ``rs``) spans the union of the two
   decompositions' device pools (``n_flat = max(N_src, N_dst)`` slots;
   destination rank ``q`` at slot ``q``, source rank ``r`` at slot
   ``r``). Each field-signature group's source blocks are stacked into
   one ``(n_flat, F, *lead, *block)`` array (device-local reshapes plus
   at most a device-to-device placement copy — never through the host).
4. ONE jitted `shard_map` program executes the plan: per scheduled
   round, every participating device gathers its padded send slab from
   its source block (per-device offsets via tiny host-built index
   tables keyed by ``lax.axis_index``), ONE ``lax.ppermute`` moves all
   slabs (a partial permutation — the round-scheduling guarantee), and
   the receivers mask-write their valid sub-box into the destination
   block. Same-device pieces run as local rounds with no collective.
   Peak HBM per device: destination block + one padded slab + the
   gather temporary — bounded by the schedule, not by the re-blocking
   skew (arXiv:2112.01075's memory-bounded redistribution shape).
5. The destination blocks are reassembled into stacked global arrays on
   the NEW grid's mesh (replicated mesh axes rebuilt by placement).

The program is a first-class collective citizen: `reshard_contract`
declares its exact permute rounds/routes/bytes, `audit_reshard_program`
proves the compiled module against them (``tools reshard run`` gates on
it, tests pin a golden HLO fixture), and `telemetry.predict_reshard`
prices it statically.

Single-controller only for now: the flat mesh assembles per-device
buffers addressable from one process. Multi-controller runs keep the
checkpoint-based elastic restore (`restore_checkpoint_elastic`), which
remains the verified fallback and the bit-identity oracle everywhere.
"""

from __future__ import annotations

import numpy as np

from ..utils.exceptions import InvalidArgumentError
from .plan import (
    ReshardPlan, build_reshard_plan, fields_of_state, live_topology,
    reshard_contract,
)

__all__ = ["reshard_state", "compile_reshard_program",
           "audit_reshard_program", "clear_program_cache"]

# compiled programs keyed by (plan fingerprint, flat device ids): a
# resize bounced back and forth (autoscaling under variable traffic)
# pays each direction's XLA compile once. LRU-bounded — a long-lived
# scheduler resizing heterogeneous tenants must not accumulate one
# executable per geometry it ever visited
_PROGRAM_CACHE_MAX = 8
_program_cache: dict = {}


def clear_program_cache() -> None:
    _program_cache.clear()


# ---------------------------------------------------------------------------
# index tables (host-built, baked into the program as constants)
# ---------------------------------------------------------------------------

def _round_tables(pieces, pad, sig, n_flat):
    """Per-device gather/write tables of one round.

    ``send[d][dev]`` indexes the padded send window into the source
    block (positions past the piece are edge-clamped garbage the
    receiver never reads); ``wsel[d][dev]`` maps each destination-block
    position to its payload position, -1 where this round contributes
    nothing (the write mask)."""
    nd = len(sig.src_block)
    send = [np.zeros((n_flat, int(pad[d])), np.int32) for d in range(nd)]
    wsel = [np.full((n_flat, int(sig.dst_block[d])), -1, np.int32)
            for d in range(nd)]
    for p in pieces:
        for d in range(nd):
            idx = p.src_start[d] + np.arange(int(pad[d]))
            send[d][p.src_rank] = np.clip(idx, 0, sig.src_block[d] - 1)
            wsel[d][p.dst_rank,
                    p.dst_start[d]:p.dst_start[d] + p.size[d]] = \
                np.arange(p.size[d])
    return send, wsel


def _local_rounds(local_pieces):
    """Schedule same-device pieces so each device copies at most one
    sub-box per local round (one gather/mask-write pass each)."""
    rounds: list = []
    for p in local_pieces:
        for used, members in rounds:
            if p.src_rank not in used:
                used.add(p.src_rank)
                members.append(p)
                break
        else:
            rounds.append(({p.src_rank}, [p]))
    out = []
    for _, members in rounds:
        nd = len(members[0].size)
        pad = tuple(max(int(p.size[d]) for p in members) for d in range(nd))
        out.append((tuple(members), pad))
    return out


# ---------------------------------------------------------------------------
# the collective program
# ---------------------------------------------------------------------------

def compile_reshard_program(plan: ReshardPlan, mesh):
    """Jit the plan's collective program over ``mesh`` (one flat ``rs``
    axis of ``plan.n_flat`` devices). Takes one
    ``(n_flat, F, *lead, *src_block)`` array per field signature and
    returns the matching ``(n_flat, F, *lead, *dst_block)`` arrays."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    n_flat = plan.n_flat
    sig_progs = []
    for sig in plan.sigs:
        wire = [( _round_tables(r.pieces, r.pad, sig, n_flat),
                  [tuple(pr) for pr in r.pairs]) for r in sig.rounds]
        local = [(_round_tables(pieces, pad, sig, n_flat), None)
                 for pieces, pad in _local_rounds(sig.local)]
        sig_progs.append((sig, wire + local))

    def _write(out, payload, wsel, me, off, nd):
        g = payload
        mask = None
        for d in range(nd):
            w = jnp.asarray(wsel[d])[me]
            g = jnp.take(g, jnp.clip(w, 0, payload.shape[off + d] - 1),
                         axis=off + d)
            mshape = [1] * out.ndim
            mshape[off + d] = int(w.shape[0])
            md = (w >= 0).reshape(mshape)
            mask = md if mask is None else (mask & md)
        return jnp.where(mask, g, out)

    def body(*blocks):
        me = lax.axis_index("rs")
        outs = []
        for (sig, rounds), src in zip(sig_progs, blocks):
            nd = len(sig.src_block)
            off = 2 + len(sig.lead)      # (slot, F, *lead, *spatial)
            out = jnp.zeros((1, len(sig.names)) + tuple(sig.lead)
                            + tuple(sig.dst_block), src.dtype)
            for (send, wsel), pairs in rounds:
                payload = src
                for d in range(nd):
                    payload = jnp.take(payload,
                                       jnp.asarray(send[d])[me],
                                       axis=off + d)
                if pairs is not None:
                    payload = lax.ppermute(payload, "rs", perm=pairs)
                out = _write(out, payload, wsel, me, off, nd)
            outs.append(out)
        return tuple(outs)

    specs = tuple(P("rs") for _ in plan.sigs)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs, check_vma=False))


# ---------------------------------------------------------------------------
# pack / unpack (device-local reshapes + placement, never through host)
# ---------------------------------------------------------------------------

def _flat_devices(plan: ReshardPlan, src_devices, dst_devices):
    """Flat-slot device list: destination ranks first (slot q = dst rank
    q), extended with source-pool devices for send-only slots when the
    source decomposition is the larger one."""
    devices = list(dst_devices)
    have = {d.id for d in devices}
    for d in src_devices:
        if len(devices) >= plan.n_flat:
            break
        if d.id not in have:
            devices.append(d)
            have.add(d.id)
    if len(devices) < plan.n_flat:
        raise InvalidArgumentError(
            f"reshard: the device pool holds {len(devices)} device(s) but "
            f"the flat transfer mesh needs {plan.n_flat}.")
    return devices[:plan.n_flat]


def _shard_on(arr, device):
    for s in arr.addressable_shards:
        if s.device.id == device.id:
            return s.data
    raise InvalidArgumentError(
        f"reshard: no addressable shard of the source array on device "
        f"{device.id} (multi-controller runs use the checkpoint path).")


def _pack_inputs(plan: ReshardPlan, state: dict, src_devices,
                 flat_devices, mesh):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_src = int(np.prod(plan.src_dims))
    sharding = NamedSharding(mesh, P("rs"))
    flats = []
    for sig in plan.sigs:
        shape = ((plan.n_flat, len(sig.names)) + tuple(sig.lead)
                 + tuple(sig.src_block))
        dtype = np.dtype(sig.dtype)
        arrs = []
        for slot in range(plan.n_flat):
            dev = flat_devices[slot]
            if slot < n_src:
                parts = [jnp.asarray(_shard_on(state[name],
                                               src_devices[slot]))
                         for name in sig.names]
                blk = jnp.stack(parts)[None]
            else:
                blk = jnp.zeros(shape[1:], dtype)[None]
            arrs.append(jax.device_put(blk, dev))
        flats.append(jax.make_array_from_single_device_arrays(
            shape, sharding, arrs))
    return flats


def _unpack_outputs(plan: ReshardPlan, outs, dst_gg):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.topology import AXIS_NAMES
    from ..utils.blockio import starts_of

    state: dict = {}
    for sig, flat in zip(plan.sigs, outs):
        by_slot = {}
        for s in flat.addressable_shards:
            by_slot[int(s.index[0].start or 0)] = s.data
        nd_s = len(sig.dst_block)
        lead = len(sig.lead)
        spec = P(*([None] * lead), *AXIS_NAMES[:nd_s])
        sharding = NamedSharding(dst_gg.mesh, spec)
        shape = tuple(sig.lead) + tuple(
            plan.dst_dims[d] * sig.dst_block[d] for d in range(nd_s))
        needed = sharding.addressable_devices_indices_map(shape)
        for fi, name in enumerate(sig.names):
            arrs = []
            for dev, idx in needed.items():
                starts = starts_of(idx)
                coords = [starts[lead + d] // sig.dst_block[d]
                          for d in range(nd_s)]
                slot = int(np.ravel_multi_index(
                    coords + [0] * (3 - nd_s), plan.dst_dims))
                arrs.append(jax.device_put(by_slot[slot][0, fi], dev))
            state[name] = jax.make_array_from_single_device_arrays(
                shape, sharding, arrs)
    return state


# ---------------------------------------------------------------------------
# audit + the top-level move
# ---------------------------------------------------------------------------

def audit_reshard_program(plan: ReshardPlan, program, flats, *,
                          optimized: bool = False, lints=None):
    """Prove a compiled redistribution program against its plan-derived
    contract (`reshard_contract`): per-round permute counts, byte-exact
    padded payloads, route attribution, no reductions/gathers — plus the
    standard implicit-grid lints. ``optimized=False`` parses the lowered
    StableHLO (trace + lower only, no second backend compile — the form
    the driver's in-run audit uses); ``tools reshard run`` and the golden
    fixture audit the optimized HLO."""
    from ..analysis import audit_program

    return audit_program(program, *flats,
                         contract=reshard_contract(plan),
                         lints=lints, optimized=optimized,
                         meta={"program": "reshard",
                               "src_dims": list(plan.src_dims),
                               "dst_dims": list(plan.dst_dims)})


def reshard_state(state: dict, new_dims, *, quiet: bool = True,
                  audit: bool = False, lints=None):
    """Re-block the live ``state`` onto ``new_dims`` entirely HBM-to-HBM
    and re-initialize the global grid to match. Returns
    ``(new_state, info)`` where ``info`` carries the plan stats
    (``rounds``, ``wire_bytes``, ``local_bytes``, ``peak_payload_bytes``)
    plus ``audit_report`` (an `analysis.AuditReport`, or None).

    The result is bit-identical to saving a sharded checkpoint and
    `restore_checkpoint_elastic`-ing it onto the new decomposition — the
    plan reuses that path's owner-map arithmetic verbatim and the
    program moves raw bytes only (asserted in tests/test_reshard.py).
    Raises (`IncoherentArgumentError` /`InvalidArgumentError`) without
    touching the grid when the move is impossible — callers
    (`runtime.ResilientRun.resize`) fall back to the checkpoint path."""
    import jax

    from ..parallel.grid import finalize_global_grid
    from ..parallel.topology import check_initialized, global_grid
    from .plan import device_pool, init_from_topology, restore_topology

    check_initialized()
    if jax.process_count() > 1:
        raise InvalidArgumentError(
            "On-device resharding runs single-controller for now "
            "(the flat transfer mesh assembles per-device buffers from "
            "one process); multi-controller runs keep the checkpoint-"
            "based elastic restore.")
    gg = global_grid()
    topo = live_topology(gg)
    plan = build_reshard_plan(topo, new_dims, fields_of_state(state))
    src_devices = list(np.asarray(gg.mesh.devices).reshape(-1))
    # the destination pool must exist BEFORE the source grid is torn
    # down: failing here leaves the caller's grid (and its fallback
    # options) fully intact
    pool = device_pool(gg)
    n_dst = int(np.prod(plan.dst_dims))
    if n_dst > len(pool):
        raise InvalidArgumentError(
            f"reshard: destination dims {plan.dst_dims} need {n_dst} "
            f"device(s); {len(pool)} available.")

    # same grid swap as `runtime.recovery.elastic_restart` — but the
    # state stays in HBM across it (arrays outlive the grid epoch)
    finalize_global_grid()
    try:
        init_from_topology(topo, nxyz=plan.nxyz_dst, dims=plan.dst_dims,
                           quiet=quiet)
        dst_gg = global_grid()
        dst_devices = list(np.asarray(dst_gg.mesh.devices).reshape(-1))
        flat_devices = _flat_devices(plan, src_devices, dst_devices)
        mesh = jax.sharding.Mesh(np.array(flat_devices), ("rs",))

        key = (plan.fingerprint(), tuple(d.id for d in flat_devices))
        program, reports = _program_cache.pop(key, (None, None))
        if program is None:
            program = compile_reshard_program(plan, mesh)
            reports = {}
        _program_cache[key] = (program, reports)   # re-insert = recent
        while len(_program_cache) > _PROGRAM_CACHE_MAX:
            _program_cache.pop(next(iter(_program_cache)))
        flats = _pack_inputs(plan, state, src_devices, flat_devices, mesh)
        report = None
        audit_error = None
        if audit:
            # the verdict is deterministic per (key, lints): a bounced
            # autoscale must not re-trace/re-parse the program inside
            # every resize's downtime window
            lkey = None if lints is None else tuple(lints)
            report = reports.get(lkey)
            if report is None:
                try:
                    report = audit_reshard_program(plan, program, flats,
                                                   lints=lints)
                    reports[lkey] = report
                except Exception as e:
                    # the audit OBSERVES — a parser failure must not
                    # push a healthy transfer onto the disk fallback
                    audit_error = f"{type(e).__name__}: {e}"
        outs = program(*flats)
        new_state = _unpack_outputs(plan, outs, dst_gg)
    except BaseException:
        # best effort: put the SOURCE grid back so the caller (the
        # driver's via="auto") can still run its checkpoint fallback
        # against a live grid — the original state arrays are untouched
        restore_topology(topo, quiet=quiet)
        raise
    info = dict(plan.stats(), audit_report=report)
    if audit_error is not None:
        info["audit_error"] = audit_error
    return new_state, info
