"""The resilient simulation driver: a jitted step function → a supervised
long run.

The reference stops at `tic`/`toc` (SURVEY §5.4: no checkpointing, no
monitoring); the chunked runners (`models/common.py`) and the sharded
block-coordinate checkpoints (`utils/checkpoint.py`) are the two hard
ingredients this driver composes into survival without a human in the loop:

    state, reports = igg.run_resilient(step_local, {"T": T, "Cp": Cp}, nt,
                                       nt_chunk=100, key="my_model",
                                       checkpoint_dir="/ckpt/run42")

Per chunk: ONE compiled program advances ``nt_chunk`` steps with the health
probe fused into its body (`runtime/health.py` — one tiny psum per chunk
boundary); the driver fetches the replicated stats vector (a tiny D2H that
doubles as the chunk drain), builds a `HealthReport`, and

- on a healthy chunk: commits the state, periodically saving an async-safe
  DOUBLE-BUFFERED sharded checkpoint (two slots + an atomically-renamed
  ``LATEST`` pointer file — a crash mid-write can never lose the previous
  good state);
- on a tripped guard (NaN/Inf, norm divergence): rolls back to the last
  good checkpoint under the bounded-retry `RecoveryPolicy`, escalating
  (chunk shrink, `on_escalate` hook) on repeated blow-ups;
- on a restore failure (corrupt slot): falls back to the OTHER slot —
  verified, not assumed, via the per-file content checksums;
- on a simulated process loss: re-inits the grid with different ``dims``
  and elastically redistributes the last good checkpoint onto it
  (`runtime/recovery.py`).

Every recovery path is exercised deterministically by the fault-injection
species of `runtime/faults.py` in tier-1 tests. Counters for each event
kind land in the telemetry metrics registry (the
``igg_health_events_total{kind=...}`` family, readable via
``igg.metrics_registry()`` / ``igg.prometheus_snapshot()``), and with an active
flight recorder (`igg.start_flight_recorder`) the driver streams its whole
lifecycle — chunk execute/compile splits, guard trips, rollback/restore
latencies, escalations, elastic restarts — as JSONL events that
`igg.run_report` reconstructs post-hoc. All instrumentation is host-side:
the compiled chunk program is bit-identical with telemetry on or off
(`tests/test_hlo_audit.py`) and the measured overhead sits under the 2%
gate (`bench_telemetry.py`).

Since the multi-run scheduler (ISSUE 8) the loop itself is a RESUMABLE
state machine: `ResilientRun` holds one supervised run's whole context
(runner cache key, checkpoint slots, snapshot writer, perf watch, audit
budgets) and `advance()` executes exactly ONE chunk-boundary iteration —
faults due now, one supervised chunk, commit or recovery. `run_resilient`
is the drain-it-to-completion loop over that machine; the
`service.MeshScheduler` interleaves `advance()` calls of MANY machines
through one device mesh (preemption is only ever at chunk boundaries, so a
job's trajectory is bit-identical however it is sliced).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import NamedTuple

from .spec import RunSpec

__all__ = ["run_resilient", "ResilientRun", "RunSpec"]

# ``t``: monotonic end of the last chunk boundary on this thread, of any
# run (a scheduler interleaves several runs on one thread): the next
# boundary charges the time since to the ``caller`` phase
_LAST_BOUNDARY = threading.local()


class _Chunk(NamedTuple):
    """One prepared chunk (`ResilientRun._prepare`)."""
    runner: object
    plan: object      # reducer plan, or None
    step: int         # first step of the chunk
    nb: int           # step after its last
    n: int
    sizes: list       # cells per field
    misses0: float    # runner-cache misses before the lookup
    build_s: float    # runner lookup (or build) host seconds


class _CheckpointSlots:
    """Double-buffered checkpoint slots under one root directory.

    Saves alternate between ``slot0``/``slot1``; after a save fully
    commits (atomic staged-directory rename inside
    `save_checkpoint_sharded`), the ``LATEST`` pointer file is replaced
    atomically (tmp + fsync + rename) to name the new last-good slot.
    Restore order is pointer target first, then the other slot — so a
    crash at ANY point (mid-save, mid-pointer-write, post-corruption)
    still finds a complete verified checkpoint."""

    SLOTS = ("slot0", "slot1")
    POINTER = "LATEST"

    def __init__(self, root):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)

    def _pointer(self) -> str:
        return os.path.join(self.root, self.POINTER)

    def latest(self):
        """Path of the last committed slot, or None."""
        try:
            with open(self._pointer()) as f:
                rec = json.load(f)
            name = rec["slot"]
        except Exception:
            return None
        return os.path.join(self.root, name) if name in self.SLOTS else None

    def candidates(self) -> list:
        """Restore order: pointer target first, then the other slot."""
        latest = self.latest()
        out = [latest] if latest else []
        for s in self.SLOTS:
            p = os.path.join(self.root, s)
            if p != latest and os.path.isdir(p):
                out.append(p)
        return out

    def save(self, state: dict, step: int) -> str:
        from ..utils.checkpoint import save_checkpoint_sharded
        from ..utils.timing import barrier

        latest = self.latest()
        if latest is None or os.path.basename(latest) == self.SLOTS[1]:
            target = os.path.join(self.root, self.SLOTS[0])
        else:
            target = os.path.join(self.root, self.SLOTS[1])
        save_checkpoint_sharded(target, state, step=step)
        import jax

        if jax.process_index() == 0:
            tmp = self._pointer() + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"slot": os.path.basename(target),
                           "step": int(step)}, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._pointer())
        barrier()  # pointer visible everywhere before anyone proceeds
        return target

    def restore(self):
        """Restore the newest usable slot onto the LIVE grid. Returns
        ``(state, step, used_fallback)``; raises `ResilienceError` when
        every slot fails (corruption is DETECTED, via the checkpoint
        layer's content checksums, never silently restored). Goes through
        the elastic restore — which delegates to the plain block-keyed
        path when the decomposition matches — so a slot written BEFORE an
        elastic restart (old ``dims``) is still restorable after one."""
        from ..utils.checkpoint import restore_checkpoint_elastic
        from ..utils.exceptions import ResilienceError

        errors = []
        for i, path in enumerate(self.candidates()):
            try:
                state, step = restore_checkpoint_elastic(path)
                return state, int(step or 0), i > 0
            except Exception as e:  # corrupt/incomplete slot: try the other
                errors.append(f"{path}: {e}")
        raise ResilienceError(
            "No checkpoint slot could be restored:\n  "
            + ("\n  ".join(errors) if errors else "(no slot written yet)"))


class ResilientRun:
    """One supervised run as a resumable, chunk-granular state machine.

    ``ResilientRun(step_local, state, nt, spec)`` performs the whole setup
    `run_resilient` used to do inline (validation, metrics endpoint,
    snapshot writer, checkpoint slots, perf watch) — a raising constructor
    leaks none of those resources. Each `advance()` call then executes ONE
    chunk-boundary iteration: heartbeat, faults due at this boundary, one
    supervised chunk, commit-or-recover; it returns True while steps
    remain. `close()` releases the run's resources (idempotent; call it on
    every exit path — `run_resilient` does so in a ``finally``).

    The machine is what makes the mesh a multiplexable resource: the
    `service.MeshScheduler` holds many of these and interleaves their
    `advance()` calls, so preemption happens only at chunk boundaries and
    every job's trajectory is bit-identical to its solo run regardless of
    the interleaving (asserted in tests/test_service.py)."""

    def __init__(self, step_local, state: dict, nt: int,
                 spec: RunSpec | None = None):
        import numpy as np

        from ..parallel.topology import check_initialized
        from ..telemetry import record_event
        from ..telemetry.hooks import note_heartbeat
        from ..utils.exceptions import InvalidArgumentError
        from .faults import NaNPoke, ProcessLoss
        from .health import GuardConfig
        from .recovery import RecoveryPolicy

        spec = spec if spec is not None else RunSpec()
        check_initialized()
        if not isinstance(state, dict) or not state:
            raise InvalidArgumentError(
                "run_resilient expects a non-empty dict of name -> stacked "
                "array (names become checkpoint keys and HealthReport "
                "entries).")
        self.spec = spec
        self.step_local = step_local
        self.state = state
        self.names = list(state)
        self.ensemble = (None if spec.ensemble is None
                         else int(spec.ensemble))
        if self.ensemble is not None:
            if self.ensemble < 1:
                raise InvalidArgumentError(
                    f"RunSpec.ensemble must be >= 1; got {spec.ensemble}.")
            for k, v in state.items():
                if v.ndim < 2 or int(v.shape[0]) != self.ensemble:
                    raise InvalidArgumentError(
                        f"ensemble={self.ensemble} expects every field to "
                        f"lead with the member axis (shape (E, ...)); "
                        f"field {k!r} has shape {tuple(v.shape)} — build "
                        "the state with models.common.ensemble_state.")
        # member-splice recovery (ensemble only): after a PARTIAL guard
        # trip the healthy members' committed chunk output (their slices
        # only) is pinned here keyed by the tripped boundary's step, and
        # re-spliced over the replay when it reaches that step again —
        # one diverging realization rolls back alone, the rest keep
        # their trajectory. A dict (not a single slot) so a second trip
        # at a DIFFERENT boundary (chunk-shrink escalation mid-replay)
        # cannot silently drop an earlier boundary's pin.
        self._pins: dict = {}
        self.guard = spec.guard if spec.guard is not None else GuardConfig()
        self.policy = (spec.policy if spec.policy is not None
                       else RecoveryPolicy())
        self.nt = int(nt)
        self.cur_chunk = max(1, int(spec.nt_chunk))
        self.checkpoint_every = max(1, int(
            spec.checkpoint_every if spec.checkpoint_every is not None
            else self.cur_chunk))
        self.pending = list(spec.faults)
        for f in self.pending:
            if isinstance(f, (NaNPoke, ProcessLoss)) \
                    and not 0 <= f.step < self.nt:
                raise InvalidArgumentError(
                    f"Fault {f} is outside the run's step range "
                    f"[0, {self.nt}).")
            if isinstance(f, NaNPoke):
                if f.name not in state:
                    raise InvalidArgumentError(
                        f"NaNPoke names unknown field {f.name!r}.")
                shape = state[f.name].shape
                # OOB scatter updates are silently DROPPED by jax — a
                # mistyped index would inject nothing and the drill would
                # pass vacuously
                if len(f.index) != len(shape) or any(
                        not 0 <= int(i) < s
                        for i, s in zip(f.index, shape)):
                    raise InvalidArgumentError(
                        f"NaNPoke index {tuple(f.index)} is outside field "
                        f"{f.name!r} of stacked shape {tuple(shape)}.")
        # auto-tuner application (RunSpec.tuned): resolve once — a bad
        # path/record must fail construction, not chunk 40 — and scope
        # the config's trace-time knobs around every advance() so chunk
        # compiles resolve them (wire dtype / coalescing / cadence are
        # read from the environment at trace time and key the runner
        # cache). Structural knobs (overlap, deep cadence in the step
        # body, ensemble stacking) belong to the setup that built
        # step_local/state — the scheduler's admission applies those
        # (`service.job.builtin_setup(tuned=)`).
        from ..telemetry.tune import resolve_tuned

        self.tuned = resolve_tuned(spec.tuned)
        self._tuned_env = None if self.tuned is None else self.tuned.env()
        # re-tune trigger (ROADMAP tuner rung c): an elastic resize or a
        # PerfWatch drift flag invalidates the applied config — the
        # driver marks it stale (`tuned_stale` flight event) and the
        # scheduler clears it at the next slice boundary
        self.tuned_stale = False
        self.tuned_stale_reason = None
        # wall-clock deadline surface (RunSpec.deadline_s): crossing the
        # budget fires ONE deadline_missed flight event + counter at the
        # next boundary — observability, never a kill
        if spec.deadline_s is not None \
                and not float(spec.deadline_s) > 0:
            raise InvalidArgumentError(
                f"RunSpec.deadline_s is a wall-clock budget in seconds "
                f"(> 0); got {spec.deadline_s!r}.")
        self.deadline_s = (None if spec.deadline_s is None
                           else float(spec.deadline_s))
        self.deadline_missed = False
        # live slack: remaining budget minus the priced cost of the
        # remaining steps, refreshed at every boundary (`_check_deadline`)
        self.deadline_slack_s = None
        self._deadline_t0 = time.monotonic()
        if spec.audit_lints is not None and not spec.audit:
            raise InvalidArgumentError(
                "audit_lints selects rules for the compile-time audit — it "
                "needs audit=True.")
        if spec.audit_lints is not None:
            # fail fast on a typo'd rule name: inside the chunk loop it
            # would only surface as a buried `audit_failed` event (the
            # audit degrades by design), silently disabling the requested
            # audit
            from ..analysis import LINT_RULES

            unknown = sorted(set(spec.audit_lints) - set(LINT_RULES))
            if unknown:
                raise InvalidArgumentError(
                    f"audit_lints: unknown lint rule(s) {unknown}; "
                    f"available: {sorted(LINT_RULES)}.")
        self._np = np
        self._note_heartbeat = note_heartbeat
        self._record_event = record_event
        self.reducers = tuple(spec.reducers)
        # --- performance oracle: model attachment + live drift detector --
        model_step_s = model_bound = model_source = None
        if spec.perf_model is not None:
            if isinstance(spec.perf_model, dict):
                model_step_s = spec.perf_model.get("step_s")
                model_bound = spec.perf_model.get("bound")
                model_source = spec.perf_model.get("profile_source")
            else:
                model_step_s = spec.perf_model
            try:
                model_step_s = float(model_step_s)
            except (TypeError, ValueError):
                model_step_s = None
            if not model_step_s or model_step_s <= 0:
                raise InvalidArgumentError(
                    "perf_model must be a telemetry.predict_step record "
                    "(with a positive 'step_s') or modeled per-step "
                    f"seconds; got {spec.perf_model!r}.")
        self._model_step_s = model_step_s
        self._model_bound = model_bound
        self._model_source = model_source
        self.watch = None
        if int(spec.perf_window) > 0:
            from ..telemetry.perfmodel import PerfWatch

            self.watch = PerfWatch(window=int(spec.perf_window),
                                   zmax=float(spec.perf_zmax),
                                   model_step_s=model_step_s)
        # the live endpoint comes up FIRST: a port conflict must fail the
        # call before any other resource (writer thread, checkpoint dirs)
        # spins up
        self.server = None
        if spec.metrics_port is not None:
            from ..telemetry.server import start_metrics_server

            self.server = start_metrics_server(
                int(spec.metrics_port),
                healthz_max_age_s=spec.healthz_max_age_s)
        elif spec.healthz_max_age_s is not None:
            raise InvalidArgumentError(
                "healthz_max_age_s needs metrics_port (it configures the "
                "/healthz endpoint the driver starts).")
        self.writer = None
        try:
            self.slots = (_CheckpointSlots(spec.checkpoint_dir)
                          if spec.checkpoint_dir is not None else None)
            if spec.snapshot_dir is not None:
                from ..io.snapshot import SnapshotWriter

                # validate the field selection NOW, not at the first
                # cadence boundary — a typo'd name must fail before step 1,
                # not 50000 steps in
                if spec.snapshot_fields is not None:
                    unknown = [f for f in spec.snapshot_fields
                               if f not in state]
                    if unknown:
                        raise InvalidArgumentError(
                            f"snapshot_fields {unknown} are not in the "
                            f"state (have {self.names}).")
                self.writer = SnapshotWriter(
                    spec.snapshot_dir, queue_depth=spec.snapshot_queue,
                    policy=spec.snapshot_policy,
                    fields=spec.snapshot_fields)
            elif spec.snapshot_every is not None \
                    or spec.snapshot_fields is not None \
                    or spec.snapshot_policy != "block" \
                    or spec.snapshot_queue != 2:
                raise InvalidArgumentError(
                    "snapshot_every/snapshot_fields/snapshot_queue/"
                    "snapshot_policy need snapshot_dir to write into.")
            self.snapshot_every = max(1, int(
                spec.snapshot_every if spec.snapshot_every is not None
                else self.cur_chunk))
            record_event("run_begin", nt=self.nt, nt_chunk=self.cur_chunk,
                         checkpoint_every=self.checkpoint_every,
                         names=self.names,
                         checkpointing=self.slots is not None,
                         faults=len(self.pending),
                         snapshots=self.writer is not None,
                         snapshot_every=(self.snapshot_every
                                         if self.writer else None),
                         reducers=len(self.reducers))
            if model_step_s is not None:
                record_event("perf_model", step_s=model_step_s,
                             bound=model_bound, source=model_source)
            if self.tuned is not None:
                record_event("tuned", model=self.tuned.model,
                             **self.tuned.knobs(),
                             predicted_step_s=self.tuned.predicted_step_s,
                             measured_step_s=self.tuned.measured_step_s,
                             speedup=self.tuned.speedup)
        except BaseException:
            # a failed setup must not leak the endpoint or the writer
            # thread
            if self.writer is not None:
                self.writer.close()
            if self.server is not None:
                from ..telemetry.server import stop_metrics_server

                stop_metrics_server()
            raise

        self.reports = []
        self.step = 0
        self.chunk_idx = 0
        self.retries = 0
        self.saves = 0
        # each distinct chunk length n is a distinct jitted program (the
        # runner cache keys on it): audit every one the run dispatches,
        # once — a cadence-clipped first chunk must not leave the
        # steady-state program unaudited. Failures get ONE retry at a
        # later boundary (transient host error != permanently-broken
        # parser).
        self._audited_ns: set = set()
        self._audit_fail_counts: dict = {}
        self._started = False
        self._finished = False
        self._closed = False

    # -- derived views -----------------------------------------------------

    @property
    def done(self) -> bool:
        """True once the run completed all ``nt`` steps (the ``run_end``
        event has been recorded)."""
        return self._finished

    def _step_tuple(self, tup):
        out = self.step_local(dict(zip(self.names, tup)))
        return tuple(out[k] for k in self.names)

    # -- recovery helpers ---------------------------------------------------

    def _save(self, st, at_step):
        import jax

        from ..telemetry.hooks import record_health_event
        from .faults import CheckpointCorruption, corrupt_checkpoint

        path = self.slots.save(st, at_step)
        record_health_event("checkpoints_saved")
        due = [f for f in self.pending
               if isinstance(f, CheckpointCorruption)
               and f.save_index == self.saves]
        for f in due:
            self.pending.remove(f)
            self._record_event("fault_injected",
                               fault="CheckpointCorruption",
                               save_index=f.save_index, corruption=f.kind,
                               target=f.target)
            # one damage event, not one per process: applied by process 0
            # only (a second bitflip would undo the first; a second delete
            # would race-crash), made visible to all before anyone reads
            if jax.process_index() == 0:
                corrupt_checkpoint(path, kind=f.kind, target=f.target,
                                   process=f.process)
        if due and jax.process_count() > 1:
            from ..utils.timing import barrier

            barrier()
        self.saves += 1

    def _elastic_recover(self, new_dims):
        from ..telemetry.hooks import record_health_event
        from ..utils.exceptions import ResilienceError
        from .recovery import elastic_restart

        errors = []
        for i, path in enumerate(self.slots.candidates()):
            try:
                st, at = elastic_restart(path, new_dims)
            except Exception as e:
                errors.append(f"{path}: {e}")
                continue
            record_health_event("restores")
            if i > 0:
                record_health_event("restore_fallbacks")
            return st, int(at or 0)
        raise ResilienceError(
            "Elastic restart failed on every checkpoint slot:\n  "
            + "\n  ".join(errors))

    # -- elastic resize (ISSUE 14: the autoscaling primitive) ---------------

    def resize(self, new_dims, *, via: str = "auto") -> dict:
        """Re-block the run onto a ``new_dims`` decomposition of the SAME
        implicit global grid, between `advance()` calls (the scheduler's
        slice boundary). Two paths, one result:

        - ``"device"`` — the on-device fast path (`reshard.reshard_state`):
          the live state re-blocks HBM-to-HBM through a contract-audited
          collective program (sequence of ppermute slice rounds), no disk
          round-trip. Single-controller; with ``RunSpec.audit`` the
          program is statically audited against its plan-derived contract
          (an ``audit`` event with ``program="reshard"``).
        - ``"checkpoint"`` — the verified fallback and bit-identity
          oracle: save the live state to the slots, then
          `restore_checkpoint_elastic` onto the new decomposition (the
          `ProcessLoss` recovery machinery, minus the lost steps — the
          live state is the save, so nothing recomputes).

        ``"auto"`` (default) tries the device path and falls back. Both
        paths end BIT-IDENTICAL (the plan reuses the elastic restore's
        owner-map arithmetic verbatim; asserted in tests/test_reshard.py),
        so the trajectory after a resize equals the unresized run's.
        Afterwards the slots re-anchor on the new decomposition, the
        rebuilt chunk programs get fresh audit budgets, the
        ``igg_reshard_{bytes,seconds,rounds}`` metrics and a ``resize``
        flight event record the move, and an applied `TunedConfig` is
        marked stale (``tuned_stale`` event — it was tuned for the OLD
        geometry). Returns the resize record (``via``, ``seconds``,
        plan stats)."""
        from ..parallel.topology import global_grid
        from ..telemetry.hooks import observe_audit, observe_reshard, \
            record_health_event
        from ..utils.exceptions import InvalidArgumentError, ResilienceError

        if via not in ("auto", "device", "checkpoint"):
            raise InvalidArgumentError(
                f"resize: via must be auto|device|checkpoint; got {via!r}.")
        if self._finished:
            raise InvalidArgumentError(
                "resize: the run already completed all its steps.")
        new_dims = tuple(int(d) for d in new_dims)
        if len(new_dims) != 3:
            raise InvalidArgumentError(
                f"resize: new_dims must be 3 ints; got {new_dims}.")
        gg = global_grid()
        if tuple(int(d) for d in gg.dims) == new_dims:
            self._record_event("resize", via="noop",
                               new_dims=list(new_dims), step=self.step)
            return {"via": "noop", "new_dims": list(new_dims)}
        # argument-level feasibility FIRST: dims that cannot decompose
        # the implicit global grid (raises IncoherentArgumentError) or
        # that exceed the device pool fail the checkpoint path
        # identically — and the elastic fallback tears the live grid
        # down before its init would fail, so reaching it with an
        # infeasible request would leave the run DEAD, not rejected
        from ..reshard import live_topology
        from ..reshard.plan import device_pool, restore_topology
        from ..utils.checkpoint import elastic_local_size

        src_topo = live_topology(gg)
        elastic_local_size(src_topo, new_dims)
        pool = device_pool(gg)
        n_new = new_dims[0] * new_dims[1] * new_dims[2]
        if n_new > len(pool):
            raise InvalidArgumentError(
                f"resize: new_dims {new_dims} need {n_new} device(s); "
                f"{len(pool)} available.")
        t0 = time.monotonic()
        info: dict = {}
        used = device_error = None
        if via in ("auto", "device"):
            try:
                from ..reshard import reshard_state

                self.state, info = reshard_state(
                    self.state, new_dims, audit=self.spec.audit,
                    lints=self.spec.audit_lints)
                used = "device"
            except Exception as e:
                if via == "device":
                    raise
                device_error = f"{type(e).__name__}: {e}"
        if used is None:
            if self.slots is None:
                raise ResilienceError(
                    f"resize to {new_dims}: no checkpoint_dir is "
                    "configured for the elastic (checkpoint) path"
                    + (f", and the on-device path failed "
                       f"({device_error})" if device_error else "")
                    + ".")
            # anchor the LIVE state first: the checkpoint path re-blocks
            # the last save, which must be this exact boundary's state
            self._save(self.state, self.step)
            try:
                self.state, self.step = self._elastic_recover(new_dims)
            except BaseException:
                # the elastic restart finalizes + re-inits BEFORE
                # restoring: a total restore failure (every slot
                # unreadable) would otherwise leave the grid on
                # new_dims with old-dims state — put the SOURCE grid
                # back so a caller treating this as a rejected request
                # (the scheduler) keeps the tenant alive
                restore_topology(src_topo, quiet=True)
                raise
            used = "checkpoint"
        dur = time.monotonic() - t0
        report = info.pop("audit_report", None)
        if report is not None:
            observe_audit(report, program="reshard")
        if info.get("audit_error"):
            self._record_event("audit_failed", program="reshard",
                               error=info.pop("audit_error"))
        # the rebuilt decomposition's chunk programs get fresh audits —
        # and the slots re-anchor so any later rollback stays on the
        # live grid (same rule as the elastic restart)
        self._audited_ns.clear()
        self._audit_fail_counts.clear()
        if self.slots is not None:
            self._save(self.state, self.step)
        record_health_event("resizes")
        observe_reshard(
            dur, via=used, new_dims=list(new_dims), step=self.step,
            rounds=info.get("rounds"), wire_bytes=info.get("wire_bytes"),
            local_bytes=info.get("local_bytes"),
            peak_payload_bytes=info.get("peak_payload_bytes"),
            **({"device_error": device_error} if device_error else {}))
        self._mark_tuned_stale("resize")
        return {"via": used, "seconds": dur, "new_dims": list(new_dims),
                **({"device_error": device_error} if device_error else {}),
                **info}

    def _mark_tuned_stale(self, reason: str) -> None:
        """Flag the applied `TunedConfig` as invalidated (a resize changed
        the geometry it was searched for; a PerfWatch drift says its
        knobs stopped winning). No-op without a tuned config; records the
        ``tuned_stale`` flight event once."""
        if self.tuned is None or self.tuned_stale:
            return
        self.tuned_stale = True
        self.tuned_stale_reason = reason
        self._record_event("tuned_stale", reason=reason,
                           model=self.tuned.model)

    def clear_tuned(self) -> None:
        """Drop the applied `TunedConfig` (the scheduler's stale-config
        reaction at a slice boundary): subsequent chunk compiles resolve
        the DEFAULT wire/coalesce/cadence environment again. Structural
        knobs the setup baked in (overlap, a deep super-step,
        ensemble stacking) persist until re-admission — this clears the
        trace-time scope."""
        self.tuned = None
        self._tuned_env = None
        self.tuned_stale = False
        self.tuned_stale_reason = None

    def apply_tuned(self, cfg) -> None:
        """Apply a (re)tuned `TunedConfig` to the LIVE run — the
        scheduler's boundary re-tune after an autoscale resize
        (`service.autoscale`). Subsequent chunk compiles resolve the
        config's trace-time knob environment; after a resize the new
        epoch's runner caches are empty, so the very next compile picks
        it up. Structural knobs (overlap, a deep cadence baked into the
        step body, ensemble stacking) are NOT re-applied — the step
        function is already built, which is why a boundary re-tune
        searches trace-time knobs only. Clears any stale flag and
        records a ``tuned`` flight event."""
        from ..telemetry.tune import TunedConfig
        from ..utils.exceptions import InvalidArgumentError

        if not isinstance(cfg, TunedConfig):
            raise InvalidArgumentError(
                f"apply_tuned takes a telemetry.TunedConfig; got "
                f"{type(cfg).__name__}.")
        self.tuned = cfg
        self._tuned_env = cfg.env()
        self.tuned_stale = False
        self.tuned_stale_reason = None
        self._record_event("tuned", model=cfg.model, **cfg.knobs(),
                           predicted_step_s=cfg.predicted_step_s,
                           measured_step_s=cfg.measured_step_s,
                           speedup=cfg.speedup)

    def reprice(self, step_s: float, *, bound=None, source=None) -> None:
        """Replace the attached perf-model unit price (seconds per nt
        unit). The autoscaler calls this after an applied resize so the
        deadline-slack computation (`_check_deadline`) and the PerfWatch
        measured/modeled ratio track the NEW geometry instead of the
        admission-time price — without it, a grown job would keep
        reading negative slack off the old price and the policy loop
        would never converge. Records a ``perf_model`` flight event."""
        from ..utils.exceptions import InvalidArgumentError

        try:
            step_s = float(step_s)
        except (TypeError, ValueError):
            step_s = 0.0
        if not step_s > 0:
            raise InvalidArgumentError(
                f"reprice: step_s must be positive modeled seconds per "
                f"step; got {step_s!r}.")
        self._model_step_s = step_s
        self._model_bound = bound
        self._model_source = source
        if self.watch is not None:
            self.watch.model_step_s = step_s
        self._record_event("perf_model", step_s=step_s, bound=bound,
                           source=source)

    # -- the chunk-boundary iteration ---------------------------------------

    def advance(self) -> bool:
        """Execute ONE chunk-boundary iteration; return True while steps
        remain (False once the run is complete). The first call performs
        the initial step-0 checkpoint save; the call that commits step
        ``nt`` records the ``run_end`` event. Preemption between calls is
        safe — this is the scheduler's slice boundary. With a tuned
        config attached (`RunSpec.tuned`) every iteration runs under the
        config's trace-time knob scope, so any chunk compile this call
        pays resolves the tuned wire/coalesce/cadence environment."""
        if self._tuned_env is not None:
            from ..telemetry.tune import _scoped_env

            with _scoped_env(self._tuned_env):
                return self._advance()
        return self._advance()

    def _advance(self) -> bool:
        if self._finished:
            return False
        if not self._started:
            self._started = True
            if self.slots is not None:
                # rollback ALWAYS possible, even before step 1
                self._save(self.state, 0)
        if self.step < self.nt:
            self._iterate()
        if self.step >= self.nt and not self._finished:
            self._note_heartbeat(self.step)
            # a run that crossed its budget inside the FINAL chunk still
            # reports it (no further boundary would check)
            self._check_deadline()
            self._record_event("run_end", completed=self.step,
                               chunks=self.chunk_idx)
            self._finished = True
        return not self._finished

    def _check_deadline(self) -> None:
        """Boundary-granular deadline watch. Every boundary of a
        deadline-budgeted run computes the LIVE SLACK — remaining budget
        minus the priced cost of the remaining steps (the attached
        `predict_step` model when one backs the run, else the PerfWatch
        warm measured baseline, else the budget alone) — stamps the
        ``igg_deadline_slack_seconds`` gauge, and records a
        ``deadline_slack`` flight event: the signal the live plane's
        deadline-slack-burn alert subscribes to, so a bust is visible as
        a trend long before the miss. Past the budget, record ONE
        ``deadline_missed`` flight event (from the same computation:
        ``budget_s < 0``) and bump ``igg_job_deadline_missed_total`` —
        the run keeps going (a deadline is an operator contract, not a
        kill switch; the scheduler journals it and `service_report`
        surfaces it)."""
        if self.deadline_s is None:
            return
        from ..telemetry.hooks import (
            note_deadline_missed, note_deadline_slack,
        )

        elapsed_s = time.monotonic() - self._deadline_t0
        budget_s = self.deadline_s - elapsed_s
        step_s = self._model_step_s
        priced_by = "perf_model" if step_s else None
        if not step_s and self.watch is not None:
            step_s = self.watch.baseline_s()
            priced_by = "measured" if step_s else None
        remaining = max(0, self.nt - self.step)
        slack_s = budget_s - (step_s * remaining if step_s else 0.0)
        self.deadline_slack_s = slack_s
        note_deadline_slack(slack_s)
        self._record_event("deadline_slack", step=self.step,
                           slack_s=slack_s, budget_s=budget_s,
                           priced_step_s=step_s, priced_by=priced_by,
                           remaining_steps=remaining)
        if not self.deadline_missed and elapsed_s > self.deadline_s:
            self.deadline_missed = True
            note_deadline_missed()
            self._record_event("deadline_missed", step=self.step,
                               deadline_s=self.deadline_s,
                               elapsed_s=elapsed_s, slack_s=slack_s)

    def _iterate(self):
        """One chunk boundary in four phases, each a host profiler span
        with stats ``chunk`` and ``step``:
        ``igg.prepare`` (`_prepare`), ``igg.dispatch`` (the chunk
        program's launch), ``igg.guard_fetch`` (the wait for the guard
        vector, which drains the chunk) and ``igg.commit`` (`_commit`;
        ``igg.perf_watch`` nests in it). The same clock reads feed
        ``igg_boundary_seconds_total{phase}``, with ``caller`` for the
        time since the thread's previous boundary (of any run) ended."""
        from ..telemetry.hooks import note_boundary_phases
        from ..utils.profiling import annotate

        t_enter = time.monotonic()
        t_left = getattr(_LAST_BOUNDARY, "t", None)
        caller = {} if t_left is None else {"caller": t_enter - t_left}
        _LAST_BOUNDARY.t = None
        tags = {"chunk": self.chunk_idx, "step": self.step}
        with annotate("igg.prepare", **tags):
            chunk = self._prepare()
        if chunk is None:  # an elastic restart took this boundary
            _LAST_BOUNDARY.t = time.monotonic()
            note_boundary_phases(**caller,
                                 prepare=_LAST_BOUNDARY.t - t_enter)
            return
        with annotate("igg.dispatch", **tags):
            t_exec0 = time.monotonic()
            out = chunk.runner(*(self.state[k] for k in self.names))
            t_sent = time.monotonic()
        with annotate("igg.guard_fetch", **tags):
            # tiny replicated fetch = the chunk drain; with reducers the
            # vector carries [health | reducer segments] from ONE psum
            # (ensemble: an (E, 2N+R) matrix — per-member rows, one psum)
            vec = self._np.asarray(out[-1])
            t_done = time.monotonic()
        with annotate("igg.commit", **tags):
            try:
                self._commit(chunk, out, vec, exec_s=t_done - t_exec0,
                             tags=tags)
            finally:
                _LAST_BOUNDARY.t = t_left = time.monotonic()
                note_boundary_phases(
                    **caller, prepare=t_exec0 - t_enter,
                    dispatch=t_sent - t_exec0, fetch=t_done - t_sent,
                    commit=t_left - t_done)

    def _prepare(self):
        """Heartbeat, deadline and due faults, then the chunk's bounds and
        its (cached) runner, audited once per distinct program. Returns
        the `_Chunk` to dispatch, or None when an elastic restart took
        the boundary."""
        np = self._np
        record_event = self._record_event

        from ..telemetry.hooks import (
            record_health_event, runner_cache_misses,
        )
        from ..utils.exceptions import ResilienceError
        from .faults import NaNPoke, ProcessLoss, poke_nan
        from .health import make_guarded_runner

        # liveness stamp at every boundary (normal commit, retry, and
        # elastic-restart paths all come back through here): the /healthz
        # age resets as long as the driver is making progress
        self._note_heartbeat(self.step)
        self._check_deadline()
        step = self.step
        # --- faults due at this boundary (chunks split on them) ----------
        for f in [f for f in self.pending
                  if isinstance(f, NaNPoke) and f.step == step]:
            self.pending.remove(f)
            self.state = dict(self.state)
            self.state[f.name] = poke_nan(self.state[f.name], f.index)
            record_event("fault_injected", fault="NaNPoke", step=f.step,
                         name=f.name)
        loss = next((f for f in self.pending
                     if isinstance(f, ProcessLoss) and f.step == step),
                    None)
        if loss is not None:
            self.pending.remove(loss)
            record_event("fault_injected", fault="ProcessLoss",
                         step=loss.step, new_dims=list(loss.new_dims))
            if self.slots is None:
                raise ResilienceError(
                    "ProcessLoss injected with no checkpoint_dir — "
                    "nothing to restart from.")
            self.state, self.step = self._elastic_recover(loss.new_dims)
            record_health_event("elastic_restarts")
            record_event("elastic_restart", new_dims=list(loss.new_dims),
                         to_step=self.step)
            # the restart rebuilds the chunk program for the NEW
            # decomposition — audit that one too (run_report's audit
            # section treats the last audit as authoritative), with fresh
            # retry budgets
            self._audited_ns.clear()
            self._audit_fail_counts.clear()
            # re-anchor the slots on the NEW decomposition right away, so
            # a guard trip before the next cadence save rolls back onto
            # the live grid instead of re-crossing the dims change
            self._save(self.state, self.step)
            return None

        # --- one supervised chunk ----------------------------------------
        nb = min(step + self.cur_chunk, self.nt)
        if self.slots is not None:  # align to the checkpoint cadence
            nb = min(nb, (step // self.checkpoint_every + 1)
                     * self.checkpoint_every)
        if self.writer is not None:  # ... and to the snapshot cadence
            nb = min(nb, (step // self.snapshot_every + 1)
                     * self.snapshot_every)
        for f in self.pending:
            if isinstance(f, (NaNPoke, ProcessLoss)) and step < f.step < nb:
                nb = f.step
        pending_pins = [s for s in self._pins if s > step]
        if pending_pins:
            # member-splice replay in flight: land exactly on the NEXT
            # pinned boundary so the healthy members' pinned chunk output
            # can be re-spliced there (an overshooting boundary would
            # strand it)
            nb = min(nb, min(pending_pins))
        n = nb - step
        state, names, spec = self.state, self.names, self.spec

        E = self.ensemble
        ndims = tuple(state[k].ndim - (1 if E else 0) for k in names)
        sizes = [int(np.prod(state[k].shape[1:] if E
                             else state[k].shape)) for k in names]
        misses0 = runner_cache_misses() if self.watch is not None else 0.0
        t_build0 = time.monotonic()
        if self.reducers:
            import jax

            from ..io.reducers import build_reducer_plan, \
                make_reduced_post_chunk
            from ..models.common import make_state_runner

            # rebuilt per boundary (cheap host work): the ownership
            # geometry follows the LIVE decomposition — an elastic restart
            # changes it — and the plan signature joins the runner key, so
            # stale compiled hooks can never serve. The plan reasons over
            # PER-MEMBER geometry (the reducer hook runs vmapped, one
            # segment set per member behind the same psum).
            plan_state = state if not E else {
                k: jax.ShapeDtypeStruct(tuple(v.shape[1:]), v.dtype)
                for k, v in state.items()}
            plan = build_reducer_plan(self.reducers, names, plan_state)
            runner = make_state_runner(
                self._step_tuple, ndims, nt_chunk=n,
                key=None if spec.key is None
                else (spec.key, "resilient-io", plan.signature),
                check_vma=spec.check_vma, unroll=spec.unroll,
                post_chunk=make_reduced_post_chunk(names, plan),
                ensemble=E)
        else:
            plan = None
            runner = make_guarded_runner(
                self._step_tuple, ndims, nt_chunk=n,
                key=None if spec.key is None else (spec.key, "resilient"),
                check_vma=spec.check_vma, unroll=spec.unroll, ensemble=E)
        t_built = time.monotonic()
        if spec.audit and n not in self._audited_ns \
                and self._audit_fail_counts.get(n, 0) < 2:
            # per distinct program, at compile time: trace+lower only —
            # the XLA executable the dispatch below builds is untouched;
            # the audit's host cost is stamped on its own event, not
            # folded into the chunk's build_s attribution
            from ..analysis import audit_chunk_program
            from ..telemetry.hooks import observe_audit

            try:
                rep_audit = audit_chunk_program(
                    runner, tuple(state[k] for k in names), names=names,
                    reducer_floats=plan.length if plan is not None else 0,
                    lints=spec.audit_lints, ensemble=E)
                observe_audit(rep_audit,
                              audit_s=time.monotonic() - t_built)
                self._audited_ns.add(n)
            except Exception as e:
                # the audit OBSERVES — a parser tripped up by a new dump
                # format must degrade to a recorded failure, never kill
                # the supervised run it watches. One retry at the next
                # boundary separates a transient host error from a
                # permanently-broken parser (whose cost must not be
                # re-paid every chunk).
                self._audit_fail_counts[n] = \
                    self._audit_fail_counts.get(n, 0) + 1
                record_event("audit_failed", error=str(e),
                             audit_s=time.monotonic() - t_built,
                             attempt=self._audit_fail_counts[n])
        return _Chunk(runner=runner, plan=plan, step=step, nb=nb, n=n,
                      sizes=sizes, misses0=misses0,
                      build_s=t_built - t_build0)

    def _commit(self, chunk, out, vec, *, exec_s: float, tags: dict):
        """Judge the fetched guard vector and record the chunk, then
        commit the state (cadence save, snapshot submit) or roll back."""
        record_event = self._record_event

        from ..telemetry.hooks import (
            record_health_event, runner_cache_misses,
        )
        from ..utils.exceptions import ResilienceError
        from ..utils.profiling import annotate
        from .health import report_from_stats

        names, spec, E = self.names, self.spec, self.ensemble
        step, nb, n, plan = chunk.step, chunk.nb, chunk.n, chunk.plan
        sizes = chunk.sizes
        nh = 2 * len(names)
        if E:
            from .health import ensemble_reports_from_stats

            member_reps = ensemble_reports_from_stats(
                vec[:, :nh], names, sizes, self.guard,
                chunk=self.chunk_idx, step_begin=step, step_end=nb)
            self.reports.extend(member_reps)
            tripped = [r.member for r in member_reps if not r.ok]
            reasons = [f"{reason}@m{r.member}" for r in member_reps
                       for reason in r.reasons]
            ok = not tripped
            rep = member_reps[0]  # chunk-level anchor (chunk/step fields)
            from ..telemetry.hooks import observe_member_health

            observe_member_health(member_reps)
        else:
            rep = report_from_stats(vec[:nh], names, sizes,
                                    self.guard, chunk=self.chunk_idx,
                                    step_begin=step, step_end=nb)
            self.reports.append(rep)
            tripped, reasons, ok = None, list(rep.reasons), rep.ok
        self.chunk_idx += 1
        record_health_event("chunks")
        # exec_s covers dispatch through the stats fetch (= the chunk
        # drain); a chunk right after a runner-cache miss also pays the
        # XLA compile inside it — run_report flags those chunks as cold
        record_event("chunk", chunk=rep.chunk, step_begin=step,
                     step_end=nb, n=n, ok=ok,
                     reasons=reasons,
                     build_s=chunk.build_s,
                     exec_s=exec_s,
                     **({"members_tripped": tripped} if E else {}))
        if self.watch is not None:
            # live drift detection: pure host arithmetic per boundary (a
            # cold chunk — its dispatch paid the XLA compile after a
            # runner-cache miss — updates gauges only)
            with annotate("igg.perf_watch", **tags):
                verdict = self.watch.observe(
                    chunk=rep.chunk, step_begin=step, step_end=nb, n=n,
                    exec_s=exec_s,
                    cold=runner_cache_misses() > chunk.misses0)
            if verdict is not None:
                record_event("perf_regression", **verdict)
                self._mark_tuned_stale("perf_drift")
        if plan is not None:
            from ..telemetry.hooks import observe_reducers

            if E:
                # each scenario streams its own probes/stats: one decoded
                # segment set per member, labeled "<label>[m<member>]"
                values = {}
                for m in range(E):
                    for label, v in plan.decode(vec[m, nh:]).items():
                        values[f"{label}[m{m}]"] = v
            else:
                values = plan.decode(vec[nh:])
            observe_reducers(nb, values, ok=ok)
            if spec.on_reduce is not None:
                spec.on_reduce(nb, values)
        if spec.on_report is not None:
            for r in (member_reps if E else (rep,)):
                spec.on_report(r)

        if ok:
            self.state = dict(zip(names, out[:-1]))
            self.step = nb
            self.retries = 0
            if self.step in self._pins:
                self._splice_pin(self.step, self._pins.pop(self.step))
            # cadence saves, plus the TERMINAL state: without the latter a
            # run whose nt is off-cadence could never be resumed from its
            # own end
            if self.slots is not None \
                    and (self.step % self.checkpoint_every == 0
                         or self.step >= self.nt):
                self._save(self.state, self.step)
            if self.writer is not None \
                    and (self.step % self.snapshot_every == 0
                         or self.step >= self.nt):
                kept = self.writer.submit(self.state, self.step)
                record_event("snapshot", step=self.step,
                             displaced=not kept)
            return

        # --- guard tripped: bounded-retry rollback ------------------------
        record_health_event("guard_trips")
        self.retries += 1
        record_event("guard_trip", step_end=nb, reasons=reasons,
                     retries=self.retries,
                     **({"members": tripped} if E else {}))
        if self.slots is None:
            raise ResilienceError(
                f"Health guard tripped at step {nb} "
                f"({', '.join(reasons)}) and no checkpoint_dir is "
                "configured — cannot roll back.")
        if self.retries > self.policy.max_retries:
            raise ResilienceError(
                f"Health guard tripped {self.retries} consecutive times "
                f"at step {nb} ({', '.join(reasons)}); retry budget "
                f"({self.policy.max_retries}) exhausted.")
        if self.policy.backoff_s:
            time.sleep(self.policy.backoff_s * 2 ** (self.retries - 1))
        if self.retries >= self.policy.shrink_chunk_after \
                and self.cur_chunk > self.policy.min_nt_chunk:
            self.cur_chunk = max(self.policy.min_nt_chunk,
                                 self.cur_chunk // 2)
            record_health_event("escalations")
            record_event("escalation", retries=self.retries,
                         nt_chunk=self.cur_chunk, step=step)
            if self.policy.on_escalate is not None:
                self.policy.on_escalate({"retries": self.retries,
                                         "nt_chunk": self.cur_chunk,
                                         "step": step})
        if E and tripped:
            # PARTIAL trip: recovery keys on the member index. Pin the
            # healthy members' committed chunk output (their slices
            # only); the whole batch replays from the last-good save
            # (members are independent under vmap, so the replay IS each
            # tripped member's solo recompute), and at the pinned
            # boundary `_splice_pin` re-asserts the healthy members'
            # pinned state — surviving realizations keep their committed
            # trajectory even if the replay were to diverge; only the
            # tripped member's rolls back. An all-members trip leaves no
            # healthy set and falls through to the classic full
            # rollback (any stale pin at this boundary is dropped).
            healthy = [m for m in range(E) if m not in tripped]
            prior = self._pins.get(nb)
            if prior is not None:
                # a second trip at the SAME boundary: members healthy in
                # BOTH attempts stay pinned; newly tripped ones drop out
                healthy = [m for m in healthy if m in prior["healthy"]]
            if healthy:
                import jax.numpy as jnp

                idx = jnp.asarray(healthy)
                self._pins[nb] = {
                    "healthy": healthy,
                    "state": {k: v[idx]
                              for k, v in zip(names, out[:-1])}}
                record_health_event("member_rollbacks")
                record_event("member_rollback", members=tripped,
                             pinned=healthy, step_end=nb)
            else:
                self._pins.pop(nb, None)
        self.state, self.step, fellback = self.slots.restore()
        record_health_event("rollbacks")
        record_health_event("restores")
        if fellback:
            record_health_event("restore_fallbacks")
        record_event("rollback", to_step=self.step, fallback=fellback,
                     retries=self.retries)

    def _splice_pin(self, at_step: int, pin: dict) -> None:
        """Finish a member-splice replay: overwrite the healthy members'
        slices of the replayed state with their PINNED chunk output (the
        committed trajectory; only those members' slices were kept). The
        replay is deterministic, so this is numerically a no-op — it is
        the isolation GUARANTEE (a healthy realization can never be
        perturbed by a neighbor's rollback), and it runs before the
        commit's cadence save so checkpoints hold the spliced state."""
        import jax.numpy as jnp

        idx = jnp.asarray(pin["healthy"])
        self.state = {
            k: v.at[idx].set(pin["state"][k])
            for k, v in self.state.items()}
        self._record_event("member_splice", members=pin["healthy"],
                           step=at_step)

    def close(self) -> None:
        """Release the run's resources (metrics endpoint, snapshot-writer
        drain) — idempotent, safe on every exit path."""
        if self._closed:
            return
        self._closed = True
        if self.server is not None:
            from ..telemetry.server import stop_metrics_server

            stop_metrics_server()
        if self.writer is not None:
            # drain on EVERY exit path (normal end, retry-budget
            # ResilienceError, a user exception out of on_report): every
            # submitted snapshot is on disk before the caller proceeds
            self.writer.close()
            self._record_event("snapshot_writer_close", **self.writer.stats)


def run_resilient(step_local, state: dict, nt: int, *,
                  spec: RunSpec | None = None, **kwargs):
    """Advance ``state`` by ``nt`` steps under health supervision with
    checkpoint-rollback recovery. Returns ``(state, reports)``.

    ``step_local(state: dict) -> dict`` advances one step on LOCAL blocks
    (inside shard_map — call `local_update_halo` for exchanges, exactly as
    in `make_state_runner` steps); ``state`` maps field names to STACKED
    global arrays — the names key the checkpoints and `HealthReport`
    entries. ``key`` (hashable) enables the runner cache across chunks
    (strongly recommended: without it every chunk recompiles).

    The knobs travel either as keywords (exactly as before — the
    historical surface) or pre-packed as ``spec=RunSpec(...)`` (what the
    multi-run scheduler's `service.JobSpec` embeds); passing both raises.
    This function is a thin shim over the resumable `ResilientRun`
    machine: construct, drain `advance()` to completion, `close()`.

    ``checkpoint_dir`` enables recovery: double-buffered sharded slots +
    last-good pointer, saved every ``checkpoint_every`` steps (default:
    every chunk) — without it a tripped guard is fatal (`ResilienceError`).
    ``guard`` (`GuardConfig`) selects the on-device guards; ``policy``
    (`RecoveryPolicy`) bounds retries and escalation; ``faults`` takes the
    deterministic injection species of `runtime.faults` (each applied
    exactly once); ``on_report`` is called with every `HealthReport`.

    The chunk schedule is split at fault steps, so injections land at
    exact step boundaries; rollback recomputes from the last good save, so
    a recovered run's final state is bit-identical to an uninterrupted one
    (asserted end-to-end in `tests/test_resilience.py`).

    ``ensemble=E`` batches E scenario members through the one supervised
    run (ISSUE 12): every state array leads with the member axis (build
    with `models.common.ensemble_state`; ``step_local`` stays the
    PER-MEMBER step — the runner vmaps it), the chunk's collective count
    stays flat in E (one E x-payload ppermute pair per axis, one
    ``f32[E·(2N+R)]`` guard psum), and the guard trips PER MEMBER: a
    partial trip pins the healthy members' committed chunk output,
    replays the batch from the last-good save and re-splices the pinned
    members at the boundary (``member_rollback``/``member_splice``
    events, ``member_rollbacks`` health counter) — one diverging
    realization rolls back alone. Reducer values stream per member
    (labels suffixed ``[m<member>]``); `HealthReport.member` carries the
    member index (E reports per chunk). Elastic restart (`ProcessLoss`)
    and `ResilientRun.resize` work under ensemble too: the
    redistribution passes the leading member axis through untouched, so
    every member re-blocks exactly like a solo field (per-member
    bit-identity vs the solo elastic run, tests/test_reshard.py).

    Output pipeline (the `implicitglobalgrid_tpu/io/` subsystem —
    O(shard) per process, never a gather): ``snapshot_dir`` enables ASYNC
    sharded snapshots every ``snapshot_every`` steps (default: every
    chunk) — `io.SnapshotWriter` copies this process's shard blocks to
    host at the boundary and a background thread commits them under
    ``snapshot_dir`` (``snapshot_fields`` restricts which fields;
    ``snapshot_queue``/``snapshot_policy`` bound the queue: ``block``
    throttles, ``drop_oldest`` sheds). ``reducers`` takes `io.Probe` /
    `io.AxisSlice` / `io.Stats` specs computed INSIDE the chunk program,
    fused into the health guard's single psum (zero extra collectives);
    decoded values stream to the flight recorder + metrics gauges and to
    ``on_reduce(step, values)`` when given. Analysis side:
    `io.open_snapshot` / `read_global`.

    ``metrics_port`` (opt-in) starts the live metrics endpoint
    (`telemetry.start_metrics_server`) for the duration of the run —
    ``/metrics`` serves the Prometheus snapshot, ``/healthz`` the age of
    the driver heartbeat; ``0`` binds an ephemeral port (read it from
    ``igg.metrics_server().port``). When a server is already live in the
    process (e.g. the scheduler's long-lived endpoint), the run ATTACHES
    to it instead of failing to bind (`telemetry.server` refcounts
    starts). ``healthz_max_age_s`` makes ``/healthz`` return 503 when the
    heartbeat is older — the wedged-driver restart signal a supervisor's
    HTTP probe acts on; size it to a few chunk durations. Binds
    127.0.0.1 — see the security note in docs/observability.md. The
    heartbeat gauges themselves are stamped at every chunk boundary
    whether or not a server runs.

    Performance oracle (`telemetry.perfmodel`, host-side only): every
    chunk boundary feeds the live drift detector — a rolling per-step
    baseline (median + MAD over ``perf_window`` chunks); a chunk whose
    robust z-score exceeds ``perf_zmax`` emits a ``perf_regression``
    flight event and bumps ``igg_perf_regressions_total``, and the
    ``igg_perf_*`` gauges (per-step seconds, model ratio, z-score) track
    every boundary. Cold chunks (the dispatch after a runner-cache miss
    pays the XLA compile) are exempt from both the test and the
    baseline. ``perf_model`` attaches a prediction — a
    `telemetry.predict_step` record or modeled per-step seconds — which
    enables the measured/modeled ratio gauge and is echoed as a
    ``perf_model`` flight event for `run_report`'s ``"perf"`` section;
    ``perf_window=0`` disables the detector entirely.

    ``audit=True`` statically audits every distinct chunk program the
    run dispatches, each ONCE at compile time
    (`analysis.audit_chunk_program`): each distinct chunk length is a
    distinct jitted program (a cadence-clipped first chunk must not
    leave the steady-state program unaudited), and an elastic restart
    re-audits the rebuilt decomposition's programs. The runner is
    traced+lowered and the StableHLO checked against the guard contract
    (exactly one f32[2N + R] psum, no gathers) plus the implicit-grid
    lints (``audit_lints`` selects rules from `analysis.LINT_RULES`;
    default all). Host-side only — the XLA executable the run dispatches
    is built exactly as without the audit (HLO-asserted in
    tests/test_hlo_audit.py, gated <2% in bench_audit.py). Findings
    stream to the flight recorder (``audit`` event — `run_report`'s
    ``"audit"`` section) and the
    ``igg_audit_findings_total{rule,severity}`` metric family; an
    error-severity finding does NOT abort the run (the audit observes,
    operators gate via the report/CLI)."""
    from ..utils.exceptions import InvalidArgumentError
    from ..utils.timing import sync

    if spec is not None and kwargs:
        raise InvalidArgumentError(
            "run_resilient: pass the knobs either pre-packed via spec= or "
            f"as keywords, not both (got spec plus {sorted(kwargs)}).")
    if spec is None:
        spec = RunSpec(**kwargs)
    run = ResilientRun(step_local, state, nt, spec)
    try:
        while run.advance():
            pass
    finally:
        run.close()
    return sync(run.state), run.reports
