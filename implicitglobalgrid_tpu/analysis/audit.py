"""The audit entry points: program -> findings, wired for humans and runs.

- `audit_program(src, *args, contract=..., lints=...)` — parse anything
  (`parse_program` forms) and run contract + lint checks: the API the
  refactored HLO-audit tests and the ``tools audit`` CLI call.
- `audit_model(name, impl=...)` — compile one model family's step program
  on the CURRENT grid, derive its contract from the static plan
  (`model_contract` = `STEP_WORKLOADS` rounds over `halo_comm_plan`),
  check it, and cross-check `telemetry.predict_step`'s collective pricing
  against what the compiler actually emitted.
- `audit_chunk_program(runner, args, names=...)` — the resilient driver's
  compile-time audit (`run_resilient(audit=True)`): parses the LOWERED
  StableHLO (trace + lower only — no second XLA compile, the chunk
  program is untouched), checks the guard-psum contract and the lints,
  and returns the report the driver streams to the flight recorder and
  the ``igg_audit_findings_total`` metric family.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field

from ..utils.exceptions import InvalidArgumentError
from .contracts import (
    CollectiveContract, SEV_ERROR, SEV_WARNING, axis_routes,
    check_contract, guard_contract, measure_axes, model_contract,
    perfmodel_crosscheck, sort_findings,
)
from .hlo import ProgramIR, parse_program
from .lints import LintConfig, default_lint_config, run_lints

__all__ = ["AuditReport", "audit_program", "audit_model",
           "audit_chunk_program"]


@dataclass(frozen=True)
class AuditReport:
    """One audited program: findings + the collective summary behind them."""

    findings: tuple
    inventory: dict
    collectives: dict
    dialect: str
    contract: CollectiveContract | None = None
    crosscheck: dict | None = None
    meta: dict = dc_field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not any(f.severity == SEV_ERROR for f in self.findings)

    @property
    def errors(self) -> int:
        return sum(1 for f in self.findings if f.severity == SEV_ERROR)

    @property
    def warnings(self) -> int:
        return sum(1 for f in self.findings if f.severity == SEV_WARNING)

    def by_rule(self) -> dict:
        out: dict = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return dict(sorted(out.items()))

    def to_json(self) -> dict:
        out = {
            "ok": self.ok,
            "dialect": self.dialect,
            "errors": self.errors,
            "warnings": self.warnings,
            "findings": [f.to_json() for f in self.findings],
            "collectives": self.collectives,
            "inventory": self.inventory,
        }
        if self.crosscheck is not None:
            cc = dict(self.crosscheck)
            cc["findings"] = [f.to_json() for f in cc.get("findings", [])]
            out["crosscheck"] = cc
        if self.meta:
            out["meta"] = self.meta
        return out


def _collective_summary(ir: ProgramIR, routes=None) -> dict:
    out = {
        "permutes": len(ir.permutes),
        "all_reduces": len(ir.all_reduces),
        "all_gathers": len(ir.all_gathers),
        "all_to_alls": len(ir.all_to_alls),
        "wire_bytes": sum(ir.wire_bytes_of(p) for p in ir.permutes),
    }
    if routes:
        # None = a permute whose source_target_pairs match no mesh-axis
        # route; an explicit sentinel keeps the JSON key unambiguous
        out["by_axis"] = {("unattributed" if a is None else str(a)): r
                          for a, r in measure_axes(ir, routes).items()}
    return out


def audit_program(src, *args, contract: CollectiveContract | None = None,
                  lints=None, lint_config: LintConfig | None = None,
                  optimized: bool = True, meta=None) -> AuditReport:
    """Parse ``src`` (program text, a Lowered/Compiled object, or a jitted
    callable plus example args — see `parse_program`) and audit it.

    ``contract=None`` skips the contract check (lints still run);
    ``lints=None`` runs every rule, ``lints=()`` none, else a tuple of
    rule names from `lints.LINT_RULES`. ``lint_config`` defaults to
    `default_lint_config()` over the live grid when one is initialized
    (grid-free otherwise — the host-only golden-fixture path)."""
    ir = parse_program(src, *args, optimized=optimized)
    findings: list = []
    if contract is not None:
        findings.extend(check_contract(ir, contract))
    if lints is None or lints:
        findings.extend(run_lints(ir, config=lint_config,
                                  rules=lints))
    routes = contract.routes if contract is not None else _maybe_routes()
    return AuditReport(
        findings=tuple(sort_findings(findings)),
        inventory=ir.inventory(),
        collectives=_collective_summary(ir, routes),
        dialect=ir.dialect,
        contract=contract,
        meta=dict(meta or {}))


def _maybe_routes():
    from ..parallel.topology import grid_is_initialized

    return axis_routes() if grid_is_initialized() else None


# ---------------------------------------------------------------------------
# model programs

def _model_program(model: str, impl: str, dtype, ensemble=None,
                   comm_every=None):
    """(runner, example args, PHYSICAL state fields in canonical order).
    With ``ensemble=E`` the runner is the E-member batched chunk
    (`make_*_run(..., ensemble=E)`) and ``args`` are the member-stacked
    arrays — ``fields`` stay the per-member state the contracts price.
    With a deep ``comm_every`` cadence the runner is the deep-halo
    SUPER-STEP (`make_*_run_deep` at one super-step per call — the grid
    must carry ``depth*k_d``-wide halos per axis); XLA tier only."""
    from ..models.common import resolve_comm_every
    from .. import models as M

    cad = resolve_comm_every(comm_every if comm_every is not None else 1)
    if cad.deep and str(impl).startswith("pallas"):
        raise InvalidArgumentError(
            f"audit_model: impl={impl!r} is incompatible with "
            f"comm_every={cad} (deep-halo stepping runs only the XLA "
            "tier — the same rule the runners enforce).")
    ce = str(cad)
    if model in ("diffusion3d", "diffusion2d"):
        ndim = 3 if model.endswith("3d") else 2
        init = M.init_diffusion3d if ndim == 3 else M.init_diffusion2d
        if cad.deep:
            T, Cp, p = M.init_diffusion3d(dtype=dtype, comm_every=ce) \
                if ndim == 3 else M.init_diffusion2d(dtype=dtype)
            if ndim == 2:
                import dataclasses

                p = dataclasses.replace(p, comm_every=ce)
            run = M.make_run_deep(p, 1, ndim=ndim, ensemble=ensemble)
        else:
            T, Cp, p = init(dtype=dtype)
            run = M.make_run(p, 1, ndim=ndim, impl=impl, ensemble=ensemble)
        args = (T, Cp)
    elif model == "acoustic3d":
        if cad.deep:
            state, p = M.init_acoustic3d(dtype=dtype, comm_every=ce)
            run = M.make_acoustic_run_deep(p, 1, ensemble=ensemble)
        else:
            state, p = M.init_acoustic3d(dtype=dtype)
            run = M.make_acoustic_run(p, 1, impl=impl, ensemble=ensemble)
        args = tuple(state)
    elif model == "stokes3d":
        if cad.deep:
            state, p = M.init_stokes3d(dtype=dtype, comm_every=ce)
            run = M.make_stokes_run_deep(p, 1, ensemble=ensemble)
        else:
            state, p = M.init_stokes3d(dtype=dtype)
            run = M.make_stokes_run(p, 1, impl=impl, ensemble=ensemble)
        args = tuple(state)
    else:
        raise InvalidArgumentError(
            f"audit_model: unknown model {model!r} (have diffusion3d, "
            "diffusion2d, acoustic3d, stokes3d).")
    fields = args
    if ensemble is not None:
        from ..models.common import ensemble_state

        args = ensemble_state(args, int(ensemble))
    return run, args, fields


def _rounds_impl(model: str, impl: str, fields) -> str:
    """The impl whose exchange ROUNDS the compiled program actually uses.

    A Pallas request silently falls back to the XLA formulation when the
    fused kernel's eligibility gate rejects the current grid/state
    (`wave_exchange_modes`/`stokes_exchange_modes` — e.g. halowidth != 1
    deep-halo grids), and the contract must follow the fallback: pricing
    the fused rounds against an XLA-round program would fail a healthy
    program — the false-failure class the retired ``contract_skipped``
    exemption existed to prevent."""
    if not str(impl).startswith("pallas"):
        return impl
    from ..parallel.topology import global_grid

    gg = global_grid()
    local = [tuple(int(s) // int(gg.dims[d]) if d < 3 else int(s)
                   for d, s in enumerate(f.shape)) for f in fields]
    if model == "acoustic3d":
        from ..ops.pallas_wave import wave_exchange_modes

        if wave_exchange_modes(gg, local) is None:
            return "xla"
    elif model == "stokes3d":
        from ..ops.pallas_stokes import stokes_exchange_modes

        if stokes_exchange_modes(gg, local) is None:
            return "xla"
    # diffusion's fused rounds equal the XLA rounds, so its fallbacks
    # never change the contract
    return impl


def audit_model(model: str, *, impl: str = "xla", dtype=None,
                wire_dtype=None, lints=None,
                crosscheck: bool = True,
                optimized: bool = True,
                ensemble: int | None = None,
                comm_every=None) -> AuditReport:
    """Compile one model family's step program on the CURRENT grid and
    audit it against its plan-derived contract.

    EVERY kernel tier gets a real contract: the fused Pallas kernels ride
    the same canonical wire schema as the XLA path
    (`ops.halo.exchange_recv_slabs_multi` — one ppermute pair per mesh
    axis per round, byte-identical payload layout), so ``impl`` only
    selects which exchange ROUNDS the contract prices
    (`StepWorkload.groups_for`: e.g. the fused acoustic pass packs all
    four fields into one round where the XLA leapfrog does two). The
    pre-schema ``impl != 'xla'`` exemption (``contract_skipped``) is
    gone — ``tools audit``'s exit-1 gate covers Pallas programs.
    ``crosscheck`` additionally proves the perf oracle's priced ppermute
    pairs and wire bytes equal the parsed program's (models outside
    `STEP_WORKLOADS` skip it).

    ``wire_dtype`` is applied to BOTH sides: the compile (scoped
    ``IGG_HALO_WIRE_DTYPE`` — the runners resolve the wire format from
    the environment at trace time; restored after, never leaked into the
    process) and the expectation (contract payload dtypes, wire bytes,
    lint config, crosscheck pricing). On a backend whose optimizer
    normalizes narrow payloads back to full precision (XLA:CPU does for
    bf16) the LOWERED module is audited instead of the optimized one —
    ``meta["lowered_for_wire_audit"]`` records the switch — so the
    documented CLI gate never false-fails a healthy program.

    ``comm_every`` (a deep per-axis cadence — int / ``"z:2,x:1"`` /
    dict) audits the DEEP-HALO SUPER-STEP program instead of the plain
    step: the compiled cycle's per-axis permute counts and k_d-wide
    payload bytes must equal the super-cycle contract
    (`model_contract(comm_every=)`), and the crosscheck proves
    `predict_step`'s per-axis amortized pricing against the emitted
    collectives. The current grid must carry the cadence's halo
    geometry (``halowidths[d] = depth*k_d``); composes with
    ``ensemble`` (the vmapped deep chunk) and per-axis ``wire_dtype``.
    XLA tier only."""
    import os

    import numpy as np

    from ..models.common import resolve_comm_every
    from ..parallel.topology import check_initialized

    check_initialized()
    dtype = np.float32 if dtype is None else dtype
    meta = {"model": model, "impl": impl}
    cad = resolve_comm_every(comm_every if comm_every is not None else 1)
    if cad.deep:
        meta["comm_every"] = str(cad)
    if ensemble is not None:
        ensemble = int(ensemble)
        meta["ensemble"] = ensemble
    saved_wire = os.environ.get("IGG_HALO_WIRE_DTYPE")
    try:
        if wire_dtype is not None:
            from ..ops.precision import resolve_wire_dtype

            policy = resolve_wire_dtype(wire_dtype)
            # the CANONICAL policy string: every accepted form (dict,
            # WirePolicy, dtype-like) round-trips through the env var the
            # runners resolve at trace time — str() of a dict would not
            os.environ["IGG_HALO_WIRE_DTYPE"] = str(policy)
            if optimized:
                import jax
                # only narrow FLOAT casts are at the mercy of the CPU
                # backend's float-normalization pass; quantized int8
                # payloads survive optimized HLO on every backend, so a
                # quant-only policy keeps the deeper post-SPMD audit
                if policy is not None and policy.casts_any_below \
                        and jax.devices()[0].platform == "cpu":
                    optimized = False
                    meta["lowered_for_wire_audit"] = (
                        "XLA:CPU normalizes narrow wire payloads back to "
                        "full precision in optimized HLO; audited the "
                        "lowered module instead")
        runner, args, fields = _model_program(model, impl, dtype,
                                              ensemble=ensemble,
                                              comm_every=comm_every)
        ir = parse_program(runner, *args, optimized=optimized)
    finally:
        if saved_wire is None:
            os.environ.pop("IGG_HALO_WIRE_DTYPE", None)
        else:
            os.environ["IGG_HALO_WIRE_DTYPE"] = saved_wire
    from ..telemetry.perfmodel import STEP_WORKLOADS

    rounds_impl = impl if cad.deep else _rounds_impl(model, impl, fields)
    if rounds_impl != impl:
        meta["rounds_impl"] = (
            f"{rounds_impl} (fused kernel ineligible on this grid/state; "
            "the step fell back to the XLA formulation and the contract "
            "follows it)")
    contract = None
    if model in STEP_WORKLOADS:
        contract = model_contract(model, fields, wire_dtype=wire_dtype,
                                  impl=rounds_impl, ensemble=ensemble,
                                  comm_every=comm_every)
    cfg = default_lint_config(
        state_dtypes={str(np.dtype(getattr(f, "dtype", "float32")))
                      for f in fields},
        wire_dtype=wire_dtype)
    rep = audit_program(ir, contract=contract, lints=lints,
                        lint_config=cfg, meta=meta)
    cc = None
    if crosscheck and model in STEP_WORKLOADS:
        cc = perfmodel_crosscheck(model, fields, ir,
                                  wire_dtype=wire_dtype, impl=rounds_impl,
                                  ensemble=ensemble,
                                  comm_every=comm_every)
    if cc is None:
        return rep
    return AuditReport(
        findings=tuple(sort_findings(list(rep.findings)
                                     + list(cc["findings"]))),
        inventory=rep.inventory, collectives=rep.collectives,
        dialect=rep.dialect, contract=rep.contract, crosscheck=cc,
        meta=rep.meta)


# ---------------------------------------------------------------------------
# the driver's compile-time audit

def audit_chunk_program(runner, args, *, names, reducer_floats: int = 0,
                        contract: CollectiveContract | None = None,
                        lints=None,
                        ensemble: int | None = None) -> AuditReport:
    """Audit a resilient chunk runner ONCE at compile time, without
    touching it: traces + lowers the jitted ``runner`` with the run's
    ``args`` and parses the StableHLO (no second backend compile — the
    XLA executable the run dispatches is built exactly as without the
    audit). The default contract is the structural guard one
    (`guard_contract`): exactly one f32[2N + R] psum, no gathers; pass an
    explicit `CollectiveContract` (e.g. from `model_contract`) to also
    pin the per-axis permute counts of a known step. ``ensemble=E``
    widens the expected guard psum to the batched ``f32[E·(2N + R)]``
    stats (still exactly one all-reduce)."""
    import numpy as np

    if contract is None:
        contract = guard_contract(len(tuple(names)), reducer_floats,
                                  ensemble=ensemble)
    state_dtypes = set()
    for a in args:
        try:
            state_dtypes.add(str(np.dtype(a.dtype)))
        except (TypeError, AttributeError):
            pass
    cfg = default_lint_config(state_dtypes=state_dtypes)
    return audit_program(runner, *args, contract=contract, lints=lints,
                         lint_config=cfg, optimized=False,
                         meta={"program": "chunk",
                               "names": list(names),
                               **({"ensemble": int(ensemble)}
                                  if ensemble else {})})
