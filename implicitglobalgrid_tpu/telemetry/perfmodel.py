"""The performance oracle: an analytical per-chunk cost model + drift watch.

PRs 3 and 5 made every run *measurable* (flight recorder, metrics,
mesh-wide aggregation); nothing could say whether a measurement was
*good*. This module is the missing judgment: a roofline over the implicit
global grid that combines

- the static halo wire plan (`ops.halo.halo_comm_plan` — bytes on wire,
  collective counts, wire dtype; already derived from shapes alone),
- a per-model step workload (stencil FLOPs + HBM traffic per cell,
  `STEP_WORKLOADS`), and
- a `MachineProfile` of MEASURED coefficients (achieved memory bandwidth,
  per-mesh-axis link bandwidth and collective latency —
  `telemetry.calibrate.calibrate_machine`; spec-based defaults exist but
  are labeled as such)

into a prediction of per-step compute time, per-axis communication time,
and exposed (un-overlapped) communication, classifying each configuration
as **latency-**, **bandwidth-**, or **compute-bound** (`predict_step`).
This is the substrate the ROADMAP's hierarchical-mesh auto-tuner needs:
picking ``comm_every`` / ``wire_dtype`` / coalescing per axis becomes a
search over this model instead of a from-scratch subsystem.

The live half is `PerfWatch`: a rolling per-chunk baseline (median + MAD
over a window, robust z-score) plus the measured/modeled ratio, driven by
`runtime/driver.py` at every chunk boundary — pure host arithmetic, zero
device work. A chunk whose per-step time drifts beyond the z threshold
emits a ``perf_regression`` flight event and the ``igg_perf_*`` gauges
feed the live ``/metrics`` endpoint; the PR-5 aggregation then
distinguishes a mesh-wide slowdown from one sick process
(`aggregate.straggler_report` ``perf_regressions``).

Everything here is host-side: the compiled chunk program is bit-identical
with the oracle on or off (tests/test_hlo_audit.py) and the per-boundary
cost is a few float ops (`bench_perf.py`, gated < 2%).
"""

from __future__ import annotations

import json
import os
from collections import deque
from dataclasses import dataclass, field as dc_field

from ..utils.exceptions import InvalidArgumentError

__all__ = ["MachineProfile", "StepWorkload", "STEP_WORKLOADS",
           "default_machine_profile", "hierarchical_machine_profile",
           "load_machine_profile", "save_machine_profile", "predict_step",
           "predict_reshard", "ReshardPrediction", "PerfWatch", "robust_z"]

_PROFILE_VERSION = 1


@dataclass(frozen=True)
class MachineProfile:
    """Measured (or default) machine coefficients the cost model consumes.

    ``membw_GBps``/``flops_G`` are PER-DEVICE achieved rates (on the
    emulated CPU mesh the virtual devices share the host's cores — a
    calibration over the live mesh measures exactly that contention,
    which is why calibrated beats spec'ed). ``axes`` maps mesh axis names
    (``gx``/``gy``/``gz``) to ``{"GBps", "latency_s"}``: the effective
    one-direction link bandwidth and the per-ppermute-PAIR launch latency
    of an exchange along that axis (both directions' concurrency is
    absorbed into the effective bandwidth — the calibration measures the
    same forward+backward pair shape the exchange issues).
    ``source`` is ``"calibrated"`` or ``"default"`` so a prediction can
    always say whether measured coefficients backed it."""

    membw_GBps: float
    flops_G: float
    axes: dict
    source: str = "default"
    device: dict | None = None
    calibrated_at: float | None = None
    meta: dict = dc_field(default_factory=dict)

    def axis(self, name: str) -> dict:
        """Link coefficients for one mesh axis (falls back to the mean of
        the calibrated axes, then to conservative defaults, so a profile
        calibrated on a 1-D mesh still prices a 3-D one)."""
        rec = self.axes.get(name)
        if rec and rec.get("GBps"):
            return rec
        have = [r for r in self.axes.values() if r and r.get("GBps")]
        if have:
            return {"GBps": sum(r["GBps"] for r in have) / len(have),
                    "latency_s": sum(r.get("latency_s", 0.0)
                                     for r in have) / len(have)}
        return {"GBps": 1.0, "latency_s": 1e-4}

    def to_json(self) -> dict:
        return {"version": _PROFILE_VERSION,
                "membw_GBps": self.membw_GBps, "flops_G": self.flops_G,
                "axes": self.axes, "source": self.source,
                "device": self.device, "calibrated_at": self.calibrated_at,
                "meta": self.meta}


def default_machine_profile(device_type: str | None = None) -> MachineProfile:
    """Spec-flavored fallback coefficients (``source="default"``) — use
    `telemetry.calibrate.calibrate_machine` for measured ones. With no
    argument, the current grid's device type is used."""
    if device_type is None:
        from ..parallel.topology import global_grid

        device_type = global_grid().device_type
    if device_type == "tpu":
        # v5e-flavored: ~800 GB/s HBM, ~45 GB/s/direction ICI per link,
        # microsecond-scale collective launch; f32 vector flops
        axes = {a: {"GBps": 45.0, "latency_s": 5e-6}
                for a in ("gx", "gy", "gz")}
        return MachineProfile(membw_GBps=800.0, flops_G=45000.0, axes=axes,
                              source="default",
                              device={"platform": "tpu"})
    # emulated CPU mesh: the 8 virtual devices share one host's cores
    axes = {a: {"GBps": 4.0, "latency_s": 3e-5} for a in ("gx", "gy", "gz")}
    return MachineProfile(membw_GBps=6.0, flops_G=6.0, axes=axes,
                          source="default",
                          device={"platform": device_type or "cpu"})


def hierarchical_machine_profile() -> MachineProfile:
    """Canned hierarchical ICI+DCN coefficients (``source="default"``):
    ``gx``/``gy`` at ICI-class rates and ``gz`` at DCN-class rates (an
    order of magnitude less bandwidth, an order of magnitude more launch
    latency — a multi-slice pod's shape). Lets the per-axis wire knobs
    (``comm_every``, ``wire_dtype``) be priced on a dev box whose real
    links are all one class — the same modeled-rescue pattern as the
    comm-avoiding bench rows. Calibrate on the real pod for measured
    coefficients."""
    axes = {"gx": {"GBps": 45.0, "latency_s": 5e-6},
            "gy": {"GBps": 45.0, "latency_s": 5e-6},
            "gz": {"GBps": 2.0, "latency_s": 5e-5}}
    return MachineProfile(membw_GBps=800.0, flops_G=45000.0, axes=axes,
                          source="default",
                          device={"platform": "tpu"},
                          meta={"preset": "hierarchical",
                                "dcn_axes": ["z"]})


def save_machine_profile(profile: MachineProfile, path) -> str:
    """Persist a profile as JSON (the file `load_machine_profile` and the
    ``tools calibrate`` CLI exchange)."""
    path = os.fspath(path)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(profile.to_json(), f, indent=1)
    return path


def load_machine_profile(path) -> MachineProfile:
    path = os.fspath(path)
    try:
        with open(path, encoding="utf-8") as f:
            rec = json.load(f)
    except (OSError, ValueError) as e:
        raise InvalidArgumentError(
            f"load_machine_profile: cannot read {path}: {e}") from e
    try:
        return MachineProfile(
            membw_GBps=float(rec["membw_GBps"]),
            flops_G=float(rec["flops_G"]),
            axes={str(k): dict(v) for k, v in rec.get("axes", {}).items()},
            source=str(rec.get("source", "calibrated")),
            device=rec.get("device"),
            calibrated_at=rec.get("calibrated_at"),
            meta=rec.get("meta", {}))
    except (KeyError, TypeError, ValueError) as e:
        raise InvalidArgumentError(
            f"load_machine_profile: {path} is not a machine profile "
            f"({e}).") from e


@dataclass(frozen=True)
class StepWorkload:
    """Per-cell step cost + exchange structure of one model family.

    ``flops_per_cell`` counts the stencil arithmetic (priced at the
    profile's STENCIL-calibrated FLOP rate — slice-heavy code, not peak
    FMA); ``hbm_passes`` the HBM traffic in array passes (bytes = passes
    * itemsize * cells: state reads + writes plus a slack pass for
    materialized intermediates). ``exchange_groups`` describes how the
    step actually calls the exchange: one tuple of FIELD INDICES per
    `local_update_halo` round (fields in one round coalesce into one
    ppermute pair per axis; separate rounds pay separate launches) —
    diffusion exchanges only T, the acoustic leapfrog does a V round
    then a P round. ``fused_exchange_groups`` are the rounds the Pallas
    FUSED pass issues when they differ (the acoustic kernel exchanges all
    four fields in ONE packed round where the XLA leapfrog does two);
    ``None`` means the tiers share the same rounds. Since the fused tier
    rides the canonical wire schema (`ops.wire`), these rounds price —
    and contract-audit — Pallas programs exactly like XLA ones
    (`groups_for`).

    ``deep_exchange_groups`` are the rounds of the deep-halo
    (``comm_every``) runner when they differ from the per-step scheme:
    the deep super-step exchanges its whole evolving state in ONE
    coalesced round per due axis (acoustic: one 4-field round replaces
    the V round + P round; Stokes: one 7-field round incl. dV), and
    ``deep_halo_depth`` is the scheme's per-sub-step dependency radius
    (slab width = depth * k_d — 2 for the Stokes PT iteration).
    Deliberate single-digit precision throughout: the model's job is
    picking the right regime and being within 2x, not reproducing a
    cycle simulator."""

    flops_per_cell: float
    hbm_passes: float
    exchange_groups: tuple = ((0,),)
    fused_exchange_groups: tuple | None = None
    deep_exchange_groups: tuple | None = None
    deep_halo_depth: int = 1

    def groups_for(self, impl: str = "xla", deep: bool = False) -> tuple:
        """The exchange rounds of one kernel tier: ``impl="xla"`` (or any
        non-Pallas spelling) prices the XLA step's rounds; a Pallas impl
        prices the fused pass's (same rounds unless the workload declares
        ``fused_exchange_groups``). ``deep=True`` prices the deep-halo
        runner's rounds (`deep_exchange_groups` when declared — the
        cadence tier is XLA-only, so ``deep`` wins over ``impl``)."""
        if deep and self.deep_exchange_groups is not None:
            return self.deep_exchange_groups
        if str(impl).startswith("pallas") \
                and self.fused_exchange_groups is not None:
            return self.fused_exchange_groups
        return self.exchange_groups


# One entry per model family in `models/` (validated against the measured
# bench configs in bench_perf.py / BENCH_ALL.json `model_ratio` fields).
STEP_WORKLOADS = {
    # flux (3 diffs, 3 muls) + divergence (5) + Cp array-div + update;
    # only T is exchanged (Cp is a constant coefficient field)
    "diffusion3d": StepWorkload(flops_per_cell=22.0, hbm_passes=4.0,
                                exchange_groups=((0,),)),
    "diffusion2d": StepWorkload(flops_per_cell=14.0, hbm_passes=4.0,
                                exchange_groups=((0,),)),
    # state (P, Vx, Vy, Vz): the leapfrog exchanges the 3 V fields in one
    # coalesced round, then P in its own round (overlapped when enabled);
    # the FUSED Pallas pass packs all four fields into ONE round, and so
    # does the deep-halo super-step (per due axis)
    "acoustic3d": StepWorkload(flops_per_cell=20.0, hbm_passes=8.0,
                               exchange_groups=((1, 2, 3), (0,)),
                               fused_exchange_groups=((0, 1, 2, 3),),
                               deep_exchange_groups=((0, 1, 2, 3),)),
    # state (P, Vx, Vy, Vz, dVx, dVy, dVz, rhog): one coalesced round of
    # the 4 wave fields per PT iteration (models/stokes.py:185); the
    # deep-halo scheme exchanges the 7 evolving fields (dV included) at
    # radius-2 slabs (StokesParams.comm_every)
    "stokes3d": StepWorkload(flops_per_cell=60.0, hbm_passes=16.0,
                             exchange_groups=((1, 2, 3, 0),),
                             deep_exchange_groups=((0, 1, 2, 3, 4, 5, 6),),
                             deep_halo_depth=2),
}


def _axis_npairs(gg, dim: int) -> int:
    """Number of directed links an exchange's ppermute pair spans along
    ``dim`` (the divisor that turns the plan's all-links ``wire_bytes``
    into the one-direction per-link payload the link model prices)."""
    from ..ops.halo import _perm_pairs

    D = int(gg.dims[dim])
    periodic = bool(gg.periods[dim])
    perm_p, perm_m = _perm_pairs(D, periodic, int(gg.disp))
    return len(perm_p) + len(perm_m)


def predict_step(model, fields, *, profile: MachineProfile | None = None,
                 comm_every=1, overlap: bool = False,
                 dims=None, coalesce=None, wire_dtype=None,
                 impl: str = "xla", ensemble: int | None = None) -> dict:
    """Predict one step's cost on the CURRENT grid for stacked ``fields``.

    ``model`` is a `STEP_WORKLOADS` key or a `StepWorkload`; ``fields``
    are the stacked state arrays (or anything with shape/dtype, incl.
    ``(A, halowidths)`` tuples / `ops.fields.Field` for candidate slab
    widths) in the model's canonical state order — the workload's
    ``exchange_groups`` index into them to price each exchange round
    exactly as the step issues it (same argument forms as
    `halo_comm_plan`). ``profile`` defaults to
    `default_machine_profile()` (pass a calibrated one for measured
    coefficients). ``comm_every`` prices the deep-halo cadence — an int
    ``k`` or a PER-AXIS spec (``"z:4,x:1"`` / dict / `CommCadence`, the
    `resolve_comm_every` spelling family): each axis's exchange (whose
    k_d-wide slabs the fields' halowidths already describe) is charged
    once per ``k_d`` steps — the latency term divides by THAT axis's
    cadence, which is exactly the per-link-class amortization the
    auto-tuner (`telemetry.tune`) searches over. A deep cadence also
    switches the priced rounds to the deep runner's
    (`StepWorkload.groups_for(deep=True)` — e.g. acoustic's one 4-field
    round per due axis instead of the per-step V + P rounds).
    ``overlap`` credits communication that hides behind interior compute
    (the interior-first step shape of `hide_communication` / the
    latency-hiding scheduler). The credit is priced from the slab
    geometry of the wire schema: only the INTERIOR fraction of the
    compute can hide the wire — the boundary-shell update (the overlap
    bands each exchanging dim peels off) must complete BEFORE the
    collectives launch, so exposed comm = max(0, comm - compute *
    interior_frac) and the returned record carries ``interior_frac``.
    ``impl`` selects the kernel tier's exchange rounds
    (`StepWorkload.groups_for` — the fused Pallas pass may group rounds
    differently, e.g. acoustic's one packed 4-field round).

    ``ensemble=E`` prices the ENSEMBLE axis (ISSUE 12): E scenario
    members batched through one chunk — compute and wire bytes scale by
    E while the collective LAUNCH count (and so the latency term) stays
    flat, which is exactly the amortization the ensemble exists for. The
    record then carries the byte-exact E-scaled totals plus the
    ``per_member_*`` fields (``per_member_step_s``, ``per_member_comm_s``,
    ``per_member_exposed_comm_s``), the solo prediction (``solo_step_s``)
    and ``ensemble_amortization`` = per-member / solo step time — the
    knob a tuner searches over E with, like any other wire knob.

    Returns a record with per-step seconds and the roofline verdict::

        {"model", "profile_source", "local_cells",
         "compute": {"flops", "hbm_bytes", "flops_s", "hbm_s", "s"},
         "comm":    {axis: {"ppermute_pairs", "per_link_bytes",
                            "latency_s", "wire_s", "s"}, ...},
         "local_copy_s", "comm_s", "exposed_comm_s",
         "step_s", "bound", "bound_detail", "terms"}

    ``bound`` is the largest cost term's class — ``"compute"`` (FLOPs),
    ``"bandwidth"`` (HBM or wire bytes; ``bound_detail`` says which), or
    ``"latency"`` (collective launches) — the knob-picking signal: a
    latency-bound config wants ``comm_every``/coalescing (and
    ``bound_detail`` names the latency-dominant AXIS's knob, e.g.
    ``comm_every[z]`` — the per-axis cadence the tuner turns), a
    bandwidth-bound one wants ``wire_dtype``, a compute-bound one is
    already at the roofline."""
    from ..ops.halo import halo_comm_plan
    from ..ops.wire import resolve_comm_every
    from ..parallel.topology import check_initialized, global_grid

    check_initialized()
    gg = global_grid()
    if isinstance(model, StepWorkload):
        work, model_name = model, "custom"
    else:
        work = STEP_WORKLOADS.get(str(model))
        if work is None:
            raise InvalidArgumentError(
                f"predict_step: unknown model {model!r} (have "
                f"{sorted(STEP_WORKLOADS)}; or pass a StepWorkload).")
        model_name = str(model)
    profile = profile if profile is not None else default_machine_profile()
    cad = resolve_comm_every(comm_every)
    E = 1
    if ensemble is not None:
        E = int(ensemble)
        if E < 1:
            raise InvalidArgumentError(
                f"predict_step: ensemble must be >= 1; got {ensemble}.")

    # one wire plan per exchange ROUND the step actually performs (fields
    # in a round coalesce; separate rounds pay separate launches), merged
    # into per-axis totals
    fields = tuple(fields)
    plan = {"axes": {}, "local_copy_by_axis": {}}
    for group in work.groups_for(impl, deep=cad.deep):
        if any(i >= len(fields) for i in group):
            raise InvalidArgumentError(
                f"predict_step: model {model_name!r} expects at least "
                f"{max(group) + 1} fields in its state order "
                f"(exchange group {group}); got {len(fields)}.")
        sub = halo_comm_plan(*(fields[i] for i in group), dims=dims,
                             coalesce=coalesce, wire_dtype=wire_dtype,
                             ensemble=ensemble)
        for axis, rec in sub["axes"].items():
            dst = plan["axes"].setdefault(
                axis, {"ppermutes": 0, "wire_bytes": 0})
            dst["ppermutes"] += rec["ppermutes"]
            dst["wire_bytes"] += rec["wire_bytes"]
        for axis, b in sub["local_copy_by_axis"].items():
            plan["local_copy_by_axis"][axis] = (
                plan["local_copy_by_axis"].get(axis, 0) + b)
    # interior cells of the primary (first) field's LOCAL block
    shape0 = _shape_of(fields[0])
    local_cells = 1
    for d, s in enumerate(shape0):
        local_cells *= s // int(gg.dims[d]) if d < 3 else s

    itemsize = _itemsize_of(fields[0])
    # compute scales with the member count; the wire plan above already
    # carries the E x payloads (same launches — the latency term below is
    # the one cost the ensemble does NOT multiply)
    flops = work.flops_per_cell * local_cells * E
    hbm_bytes = work.hbm_passes * itemsize * local_cells * E
    flops_s = flops / (profile.flops_G * 1e9)
    hbm_s = hbm_bytes / (profile.membw_GBps * 1e9)
    compute_s = max(flops_s, hbm_s)

    axis_dims = {"gx": 0, "gy": 1, "gz": 2}
    comm = {}
    lat_total = wire_total = 0.0
    for axis, rec in plan["axes"].items():
        coeff = profile.axis(axis)
        pairs = rec["ppermutes"] / 2.0
        # PER-AXIS amortization: this axis's exchange fires once per its
        # OWN cadence (the k_d-wide slabs are already in the plan's
        # bytes, so per-step wire bytes stay flat while launches divide)
        k_ax = cad.for_dim(axis_dims[axis])
        npairs = _axis_npairs(gg, axis_dims[axis])
        per_link = (rec["wire_bytes"] / npairs) if npairs else 0.0
        lat_s = pairs * float(coeff.get("latency_s", 0.0)) / k_ax
        wire_s = per_link / (float(coeff["GBps"]) * 1e9) / k_ax
        comm[axis] = {"ppermute_pairs": pairs, "per_link_bytes": per_link,
                      "comm_every": k_ax,
                      "latency_s": lat_s, "wire_s": wire_s,
                      "s": lat_s + wire_s}
        lat_total += lat_s
        wire_total += wire_s
    # self-neighbor local slab swaps never touch the wire: they are HBM
    # traffic (read + write) at the memory-bandwidth coefficient,
    # amortized per axis like the collectives they stand in for
    local_copy_s = sum(
        2.0 * b / (profile.membw_GBps * 1e9) / cad.for_dim(axis_dims[a])
        for a, b in plan["local_copy_by_axis"].items())
    comm_s = lat_total + wire_total + local_copy_s
    # interior-first overlap credit, priced from the slab geometry: each
    # exchanging dim peels a 2*ol-deep boundary shell off the local block
    # that must compute BEFORE the collectives launch — only the interior
    # remainder schedules under them
    interior_frac = 1.0
    if overlap:
        interior = 1
        for d in range(min(3, len(shape0))):
            n_d = shape0[d] // int(gg.dims[d])
            D = int(gg.dims[d])
            if D > 1 or bool(gg.periods[d]):
                n_d = max(0, n_d - 2 * int(gg.overlaps[d]))
            interior *= n_d
        interior_frac = interior / max(1, local_cells)
    exposed = max(0.0, comm_s - compute_s * interior_frac) if overlap \
        else comm_s
    step_s = compute_s + exposed

    # roofline verdict: the largest EXPOSED term names the regime
    scale = (exposed / comm_s) if (overlap and comm_s > 0) else 1.0
    terms = {"flops_s": flops_s, "hbm_s": hbm_s,
             "latency_s": lat_total * scale,
             "wire_s": (wire_total + local_copy_s) * scale}
    worst = max(terms, key=terms.get)
    bound = {"flops_s": "compute", "hbm_s": "bandwidth",
             "latency_s": "latency", "wire_s": "bandwidth"}[worst]
    detail = {"flops_s": "flops", "hbm_s": "hbm",
              "latency_s": "collective-launch", "wire_s": "wire"}[worst]
    if worst == "latency_s" and comm:
        # name the latency-DOMINANT axis's knob: the verdict points at
        # the per-axis cadence the auto-tuner will actually turn
        # ("comm_every[z]"), not an undifferentiated global setting
        dom = max(comm, key=lambda a: comm[a]["latency_s"])
        detail = f"comm_every[{'xyz'[axis_dims[dom]]}]"
    rec = {
        "model": model_name,
        "profile_source": profile.source,
        "local_cells": local_cells,
        "ensemble": E,
        "comm_every": str(cad),
        "compute": {"flops": flops, "hbm_bytes": hbm_bytes,
                    "flops_s": flops_s, "hbm_s": hbm_s, "s": compute_s},
        "comm": comm,
        "local_copy_s": local_copy_s,
        "comm_s": comm_s,
        "interior_frac": interior_frac,
        "exposed_comm_s": exposed,
        "step_s": step_s,
        "bound": bound,
        "bound_detail": detail,
        "terms": terms,
    }
    if E > 1:
        # the priced amortization the ROADMAP auto-tuner searches over E
        # with: per-member cost vs the solo prediction of the SAME config
        # (pure host arithmetic — one recursive plan merge, no devices)
        solo = predict_step(model, fields, profile=profile,
                            comm_every=comm_every, overlap=overlap,
                            dims=dims, coalesce=coalesce,
                            wire_dtype=wire_dtype, impl=impl)
        rec["per_member_step_s"] = step_s / E
        rec["per_member_comm_s"] = comm_s / E
        rec["per_member_exposed_comm_s"] = exposed / E
        rec["solo_step_s"] = solo["step_s"]
        rec["ensemble_amortization"] = (
            (step_s / E) / solo["step_s"] if solo["step_s"] > 0 else 1.0)
    return rec


class ReshardPrediction(dict):
    """`predict_reshard`'s record — a plain dict (JSON-serializes
    unchanged, every existing ``rec["seconds"]`` consumer keeps working)
    that ALSO carries the one break-even arithmetic the autoscaler,
    ``tools reshard plan``, and `service_report` share. Keeping the
    amortization here, next to the transfer price it amortizes, means
    the three consumers cannot drift on it."""

    def amortized_break_even_steps(self, nt_remaining,
                                   old_step_s, new_step_s) -> dict:
        """Amortize this reshard's one-time cost over the steady-state
        per-step gain of the new geometry. ``nt_remaining`` is the steps
        (nt units) left in the job's horizon; ``old_step_s`` /
        ``new_step_s`` are the per-unit prices on the current and
        candidate decompositions (same source — both modeled or both
        measured — or the ratio lies).

        Returns a JSON-able record: ``gain_s_per_step`` (old - new;
        negative = the move is a slowdown), ``break_even_steps``
        (reshard seconds / gain — ``None`` when there is no gain to
        amortize against), ``within_horizon`` (the break-even lands
        inside ``nt_remaining`` — the autoscaler's grow gate), and
        ``net_gain_s`` (what the move is worth over the whole remaining
        horizon, transfer cost included; for a shrink this is the
        priced slowdown the job must be able to afford)."""
        reshard_s = float(self["seconds"])
        old_step_s = float(old_step_s)
        new_step_s = float(new_step_s)
        nt_remaining = max(0, int(nt_remaining))
        gain = old_step_s - new_step_s
        break_even = reshard_s / gain if gain > 0 else None
        return {
            "reshard_s": reshard_s,
            "old_step_s": old_step_s,
            "new_step_s": new_step_s,
            "gain_s_per_step": gain,
            "break_even_steps": break_even,
            "nt_remaining": nt_remaining,
            "within_horizon": bool(break_even is not None
                                   and break_even <= nt_remaining),
            "net_gain_s": gain * nt_remaining - reshard_s,
        }


def predict_reshard(plan, *,
                    profile: MachineProfile | None = None
                    ) -> ReshardPrediction:
    """Static price of one on-device reshard program
    (`reshard.build_reshard_plan` output) — the `halo_comm_plan`-style
    accounting of the elastic resize (ISSUE 14): per scheduled round, one
    collective launch (the latency term) plus the round's padded
    per-device payload over the link bandwidth; same-device pieces are
    HBM read+write traffic at the memory-bandwidth coefficient. Link
    coefficients come from `MachineProfile.axis("rs")` — the flat
    transfer mesh crosses arbitrary mesh links, so the mean of the
    calibrated axes is the honest single number.

    Returns a `ReshardPrediction` — a dict ``{"rounds", "wire_bytes",
    "local_bytes", "peak_payload_bytes", "latency_s", "wire_s",
    "local_s", "seconds", "profile_source"}`` whose
    `amortized_break_even_steps` method is the ONE place the break-even
    arithmetic lives (autoscaler, ``tools reshard plan``, and
    `service_report` all call it). The DISK path this replaces pays the sharded
    save + elastic restore instead — `bench_reshard.py` measures both
    and gates ``reshard_vs_disk_speedup >= 1.0``; this record is the
    model-side anchor the perfdb trajectory watches."""
    if profile is None:
        from ..parallel.topology import grid_is_initialized

        profile = (default_machine_profile() if grid_is_initialized()
                   else default_machine_profile("cpu"))
    coeff = profile.axis("rs")
    per_round = [b for sig in plan.sigs for b in sig.round_payload_bytes]
    latency_s = len(per_round) * float(coeff.get("latency_s", 0.0))
    wire_s = sum(b / (float(coeff["GBps"]) * 1e9) for b in per_round)
    local_s = 2.0 * plan.local_bytes / (profile.membw_GBps * 1e9)
    return ReshardPrediction({
        "rounds": plan.rounds,
        "wire_bytes": plan.wire_bytes,
        "local_bytes": plan.local_bytes,
        "peak_payload_bytes": plan.peak_payload_bytes,
        "latency_s": latency_s,
        "wire_s": wire_s,
        "local_s": local_s,
        "seconds": latency_s + wire_s + local_s,
        "profile_source": profile.source,
    })


def _unwrap_field(f):
    """The bare array-like of a `halo_comm_plan`-style field argument:
    `ops.fields.Field` and ``(A, halowidths)`` tuples (the per-candidate
    slab-width form the auto-tuner prices with) unwrap to their array."""
    from ..ops.fields import Field

    if isinstance(f, Field):
        return f.A
    if isinstance(f, tuple) and len(f) == 2 and hasattr(f[0], "shape") \
            and not hasattr(f[1], "shape"):
        return f[0]
    return f


def _shape_of(f) -> tuple:
    return tuple(int(s) for s in _unwrap_field(f).shape)


def _itemsize_of(f) -> int:
    import numpy as np

    try:
        return int(np.dtype(_unwrap_field(f).dtype).itemsize)
    except Exception:
        return 4


def robust_z(value: float, history, *, rel_floor: float = 0.02,
             min_samples: int = 2) -> tuple:
    """The house robust z-score: ``(z, median, mad)`` of ``value``
    against ``history`` (an iterable of floats), with

        z = (value - median) / max(1.4826 * MAD, rel_floor * median, 1e-12)

    — the one estimator shared by `PerfWatch` (in-driver drift detection)
    and `telemetry.live.LiveAggregate` (observer-side tailing), so the
    two planes can never disagree on what counts as a regression. Returns
    ``(None, None, None)`` before ``min_samples`` history entries."""
    from statistics import median

    hist = list(history)
    if len(hist) < max(2, int(min_samples)):
        return None, None, None
    med = median(hist)
    mad = median([abs(x - med) for x in hist])
    sigma = max(1.4826 * mad, rel_floor * med, 1e-12)
    return (float(value) - med) / sigma, med, mad


class PerfWatch:
    """Live drift detector over per-chunk step times (host-side only).

    The driver feeds it one observation per chunk boundary
    (``observe(...)``); it maintains a rolling baseline of per-STEP
    execution time (median + MAD over ``window`` chunks — robust to the
    occasional slow fetch) and a modeled ratio when a prediction is
    given. An observation whose robust z-score

        z = (per_step - median) / max(1.4826 * MAD, rel_floor * median)

    exceeds ``zmax`` (after ``min_samples`` warm-up chunks) returns a
    regression record the driver emits as a ``perf_regression`` flight
    event. Chunks marked ``cold`` (the dispatch paid an XLA compile after
    a runner-cache miss) update the gauges but neither test nor pollute
    the baseline. Every observation lands in the ``igg_perf_*`` gauges
    (`telemetry.hooks.observe_perf`), so the live ``/metrics`` endpoint
    always shows the current per-step time, model ratio, and z-score."""

    def __init__(self, *, window: int = 16, zmax: float = 4.0,
                 model_step_s: float | None = None, min_samples: int = 5,
                 rel_floor: float = 0.02):
        if window < 2:
            raise InvalidArgumentError(
                f"PerfWatch needs window >= 2 (got {window}).")
        self.window = int(window)
        self.zmax = float(zmax)
        # clamped to the window: a deque of maxlen=window can never hold
        # min_samples > window entries, which would silently disable the
        # z-test for small perf_window values
        self.min_samples = max(2, min(int(min_samples), self.window))
        self.rel_floor = float(rel_floor)
        self.model_step_s = (None if model_step_s is None
                             else float(model_step_s))
        self._hist: deque = deque(maxlen=self.window)
        self.regressions = 0

    def baseline_s(self) -> float | None:
        """The current warm per-step baseline (median of the rolling
        window), or None before ``min_samples`` warm chunks — the
        measured-price fallback the driver's deadline-slack computation
        uses when no `predict_step` model was attached."""
        from statistics import median

        if len(self._hist) < self.min_samples:
            return None
        return float(median(self._hist))

    def observe(self, *, chunk, step_begin, step_end, n, exec_s,
                cold: bool = False) -> dict | None:
        """One chunk boundary. Returns the regression record (or None)."""
        from .hooks import observe_perf

        per_step = float(exec_s) / max(1, int(n))
        ratio = (per_step / self.model_step_s
                 if self.model_step_s else None)
        z, med, mad = robust_z(per_step, self._hist,
                               rel_floor=self.rel_floor,
                               min_samples=self.min_samples)
        verdict = None
        if z is not None:
            if not cold and z > self.zmax:
                self.regressions += 1
                verdict = {"chunk": chunk, "step_begin": step_begin,
                           "step_end": step_end, "per_step_s": per_step,
                           "baseline_s": med, "mad_s": mad, "z": z,
                           "ratio": ratio}
        if not cold:
            self._hist.append(per_step)
        observe_perf(per_step, ratio=ratio, z=z,
                     regression=verdict is not None)
        return verdict
