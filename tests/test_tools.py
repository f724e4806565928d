"""Tests of the query layer — port of `test/test_tools.jl` ideas: global
sizes incl. staggered-array overloads (`test_tools.jl` / reference
`tools.jl:24-59`), and the x_g/y_g/z_g coordinate math with staggering and
periodic wrap, swept over simulated shard coordinates (the reference's
simulated-topology technique, `test_tools.jl:116-163`)."""

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg


def test_nx_g_plain_and_staggered():
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    assert (igg.nx_g(), igg.ny_g(), igg.nz_g()) == (8, 8, 8)
    A = np.zeros((5, 5, 5))
    Vx = np.zeros((6, 5, 5))
    Vy = np.zeros((5, 6, 5))
    Vz = np.zeros((5, 5, 6))
    assert igg.nx_g(A) == 8 and igg.nx_g(Vx) == 9
    assert igg.ny_g(Vy) == 9 and igg.ny_g(Vx) == 8
    assert igg.nz_g(Vz) == 9
    # stacked-global arrays give the same answers
    assert igg.nx_g(igg.zeros_g()) == 8
    assert igg.nx_g(igg.zeros_g((6, 5, 5))) == 9


def test_x_g_doctest_values():
    # reference doctest (tools.jl:67-96): lx=4, nx=ny=nz=3, 1 "process"
    igg.init_global_grid(3, 3, 3, dimx=1, dimy=1, dimz=1, quiet=True)
    dx = 4 / (igg.nx_g() - 1)
    assert dx == 2.0
    A = np.zeros((3, 3, 3))
    Vx = np.zeros((4, 3, 3))
    assert [igg.x_g(i, dx, A) for i in range(3)] == [0.0, 2.0, 4.0]
    assert [igg.x_g(i, dx, Vx) for i in range(4)] == [-1.0, 1.0, 3.0, 5.0]
    assert [igg.y_g(i, dx, np.zeros((3, 4, 3))) for i in range(4)] == [-1.0, 1.0, 3.0, 5.0]
    assert [igg.z_g(i, dx, np.zeros((3, 3, 4))) for i in range(4)] == [-1.0, 1.0, 3.0, 5.0]


def test_x_g_multi_shard_coverage():
    # dims=(3,1,1), nx=4, ol=2: nxyz_g = 3*2+2 = 8; block c covers (c*2 .. c*2+3)
    igg.init_global_grid(4, 3, 3, dimx=3, dimy=1, dimz=1, quiet=True)
    assert igg.nx_g() == 8
    A = np.zeros((4, 3, 3))
    for c in range(3):
        xs = [igg.x_g(i, 1.0, A, coords=c) for i in range(4)]
        assert xs == [c * 2 + i for i in range(4)]


def test_x_g_periodic_wrap():
    # periodic: ghost-cell shift by -dx then wrap into [0, nx_g*dx) (tools.jl:102-104)
    igg.init_global_grid(4, 3, 3, dimx=3, dimy=1, dimz=1, periodx=1, quiet=True)
    assert igg.nx_g() == 6
    A = np.zeros((4, 3, 3))
    assert [igg.x_g(i, 1.0, A, coords=0) for i in range(4)] == [5.0, 0.0, 1.0, 2.0]
    assert [igg.x_g(i, 1.0, A, coords=2) for i in range(4)] == [3.0, 4.0, 5.0, 0.0]
    # every global cell covered exactly once by the interior cells
    cover = sorted(
        igg.x_g(i, 1.0, A, coords=c) for c in range(3) for i in range(1, 3)
    )
    assert cover == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0]


def test_x_g_stacked_equals_local():
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    T = igg.zeros_g()
    A = np.zeros((5, 5, 5))
    for c in range(2):
        for i in range(5):
            assert igg.x_g(c * 5 + i, 0.5, T) == igg.x_g(i, 0.5, A, coords=c)
            assert igg.y_g(c * 5 + i, 0.5, T) == igg.y_g(i, 0.5, A, coords=c)


def test_coords_g_broadcastable():
    igg.init_global_grid(5, 5, 5, dimx=2, dimy=2, dimz=2, quiet=True)
    T = igg.zeros_g()
    x, y, z = igg.coords_g(1.0, 1.0, 1.0, T)
    assert x.shape == (10, 1, 1) and y.shape == (1, 10, 1) and z.shape == (1, 1, 10)
    assert float(x[5, 0, 0]) == igg.x_g(5, 1.0, T)
    # staggered
    Vx = igg.zeros_g((6, 5, 5))
    xs, _, _ = igg.coords_g(1.0, 1.0, 1.0, Vx)
    assert xs.shape == (12, 1, 1)
    assert float(xs[0, 0, 0]) == igg.x_g(0, 1.0, Vx)


def test_x_g_vec_matches_scalar():
    igg.init_global_grid(4, 4, 4, dimx=2, dimy=2, dimz=2, periody=1, quiet=True)
    T = igg.zeros_g()
    xv = np.asarray(igg.x_g_vec(0.25, T))
    yv = np.asarray(igg.y_g_vec(0.25, T))
    for i in range(8):
        assert xv[i] == igg.x_g(i, 0.25, T)
        assert yv[i] == igg.y_g(i, 0.25, T)


@pytest.mark.parametrize("n,d", [(16, 10.0 / 13), (256, 10.0 / 253)])
def test_periodic_halo_coordinates_wrap_onto_partners(n, d):
    """On a periodic axis each halo cell sits exactly on its periodic
    partner's coordinate: the wrap runs in cell units, so ``dx =
    lx/(nx_g-1)`` rounding cannot move the last halo cell to ``nx_g*dx``
    (it did for these sizes, giving the initial conditions an
    inconsistent upper halo)."""
    igg.init_global_grid(n, 4, 4, dimx=1, periodx=1, quiet=True)
    x = np.asarray(igg.x_g_vec(d, igg.zeros_g())).ravel()
    assert x[0] == x[n - 2] and x[n - 1] == x[1] == 0.0


def test_simulated_topology_mutation():
    # the reference mutates the (intentionally mutable) grid vectors to fake
    # topologies (shared.jl:57 comment; test_tools.jl:116-134) — same here.
    igg.init_global_grid(4, 4, 4, dimx=1, dimy=1, dimz=1, quiet=True)
    gg = igg.global_grid()
    gg.dims[:] = [3, 3, 3]
    gg.nxyz_g[:] = gg.dims * (gg.nxyz - gg.overlaps) + gg.overlaps * (gg.periods == 0)
    assert igg.nx_g() == 3 * 2 + 2
    A = np.zeros((4, 4, 4))
    # sweep all simulated coordinates: consistent overlap between neighbors
    for c in range(2):
        right_edge = [igg.x_g(i, 1.0, A, coords=c) for i in (2, 3)]
        left_edge = [igg.x_g(i, 1.0, A, coords=c + 1) for i in (0, 1)]
        assert right_edge == left_edge


def test_tic_toc():
    igg.init_global_grid(4, 4, 4, quiet=True)
    igg.tic()
    t = igg.toc()
    assert t >= 0.0
    with pytest.raises(Exception):
        igg.finalize_global_grid(); igg.tic()


def test_layout_override_disambiguates_small_blocks():
    """Explicit layout= kwarg vs the `local_shape_of` inference heuristic:
    a block whose size equals dims*nxyz is read as stacked by default; the
    override forces the local reading (and validates stacked divisibility)."""
    from implicitglobalgrid_tpu.ops.fields import local_shape_of
    from implicitglobalgrid_tpu.utils.exceptions import (
        IncoherentArgumentError, InvalidArgumentError,
    )

    igg.init_global_grid(4, 4, 4, dimx=2, dimy=1, dimz=1, quiet=True)
    # ambiguous: 8 == 2*4 (stacked) but could be a heavily staggered local
    assert local_shape_of((8, 4, 4)) == (4, 4, 4)            # inferred stacked
    assert local_shape_of((8, 4, 4), "local") == (8, 4, 4)
    assert local_shape_of((8, 4, 4), "stacked") == (4, 4, 4)
    # nx_g follows: nxyz_g = 2*(4-2)+2 = 6
    A = np.zeros((8, 4, 4))
    assert igg.nx_g(A) == 6
    assert igg.nx_g(A, layout="local") == 6 + (8 - 4)
    with pytest.raises(IncoherentArgumentError):
        local_shape_of((7, 4, 4), "stacked")
    with pytest.raises(InvalidArgumentError):
        local_shape_of((8, 4, 4), "global")


@pytest.mark.audit
def test_audit_cli_json_schema_and_model_smoke(capsys):
    """`tools audit` smoke on both main model families in one invocation:
    rc 0, and the --json schema carries the contract verdict, the
    findings list, the collective summary, and the perfmodel crosscheck
    per program."""
    import json

    from implicitglobalgrid_tpu.tools import _cli

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, periodx=1,
                         periody=1, periodz=1, quiet=True)
    rc = _cli(["audit", "diffusion3d", "acoustic3d", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["ok"] is True
    assert [p["name"] for p in out["programs"]] \
        == ["diffusion3d", "acoustic3d"]
    for prog in out["programs"]:
        assert prog["ok"] is True and prog["dialect"] == "hlo"
        assert prog["errors"] == 0 and prog["findings"] == []
        assert prog["collectives"]["all_gathers"] == 0
        assert prog["collectives"]["permutes"] > 0
        assert prog["crosscheck"]["ok"] is True
        assert set(prog["crosscheck"]["axes"]) == {"gx", "gy", "gz"}
        assert isinstance(prog["inventory"], dict)
    # the human-readable form of the same audit also exits 0
    assert _cli(["audit", "diffusion3d"]) == 0
    assert "diffusion3d: OK" in capsys.readouterr().out


@pytest.mark.audit
def test_audit_cli_exit_1_on_contract_violation(tmp_path, capsys):
    """An injected contract violation (the golden single-axis exchange
    checked against a contract demanding a guard psum it doesn't have)
    EXITS 1 and names the broken rule — host-only, no grid, no compile."""
    import json
    import os
    import shutil

    from implicitglobalgrid_tpu.tools import _cli

    fixture = os.path.join(os.path.dirname(__file__), "data", "hlo",
                           "exchange_single_axis.hlo.txt")
    hlo = tmp_path / "prog.hlo.txt"
    shutil.copy(fixture, hlo)
    contract = tmp_path / "contract.json"
    contract.write_text(json.dumps(
        {"allreduces": 1, "allreduce_payload": ["f32", 4]}))
    rc = _cli(["audit", "--hlo", str(hlo), "--contract", str(contract),
               "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["ok"] is False
    rules = [f["rule"] for f in out["programs"][0]["findings"]]
    assert "allreduce-count" in rules
    assert all(f["severity"] in ("error", "warning", "info")
               for f in out["programs"][0]["findings"])
    # without the contract the same dump lints clean -> rc 0
    assert _cli(["audit", "--hlo", str(hlo), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["ok"] is True
    # --wire-dtype applies to captured dumps too: this dump's payloads
    # are f32, so a claimed bf16 wire is a caught downcast-missing error
    rc = _cli(["audit", "--hlo", str(hlo), "--wire-dtype", "bfloat16",
               "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert [f["rule"] for f in out["programs"][0]["findings"]] \
        == ["wire-downcast-missing"]


@pytest.mark.audit
def test_audit_cli_argument_validation():
    from implicitglobalgrid_tpu.tools import _cli
    from implicitglobalgrid_tpu.utils.exceptions import (
        InvalidArgumentError,
    )

    with pytest.raises(InvalidArgumentError):
        _cli(["audit"])  # neither models nor --hlo
    with pytest.raises(InvalidArgumentError):
        _cli(["audit", "diffusion3d", "--hlo", "x.txt"])  # both


@pytest.mark.service
def test_jobs_cli_submit_list_status_control(tmp_path, capsys):
    """`tools jobs` smoke, exit codes included: submit runs a
    JSON-described queue through one scheduler (rc 1 when a job fails —
    here an unsatisfiable grid fails at admission while the good job
    completes), list/status answer post-hoc from the journal (rc 3 for
    an unknown name), cancel/drain file control requests (rc 4 for an
    already-finished job)."""
    import json

    from implicitglobalgrid_tpu.tools import _cli

    fd = str(tmp_path / "fd")
    queue = tmp_path / "queue.json"
    queue.write_text(json.dumps({"policy": "fifo", "jobs": [
        {"name": "ok", "model": "diffusion3d", "dtype": "float64",
         "nt": 4, "grid": {"nx": 6, "ny": 6, "nz": 6, "dimx": 2,
                           "dimy": 2, "dimz": 1},
         "run": {"nt_chunk": 2}},
        # 16 shards > the 8-device pool: fails at admission, no compile
        {"name": "toobig", "model": "diffusion3d", "nt": 4,
         "grid": {"nx": 6, "ny": 6, "nz": 6, "dimx": 16, "dimy": 1,
                  "dimz": 1}},
    ]}))
    rc = _cli(["jobs", "submit", str(queue), "--flight-dir", fd,
               "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1  # one job failed -> the batch entry is not ok
    assert out["ok"] is False
    by_name = {j["name"]: j for j in out["jobs"]}
    assert by_name["ok"]["state"] == "done"
    assert by_name["ok"]["step"] == 4
    assert by_name["toobig"]["state"] == "failed"
    assert "InvalidArgumentError" in by_name["toobig"]["error"]

    assert _cli(["jobs", "list", fd]) == 0
    listing = capsys.readouterr().out
    assert "ok" in listing and "toobig" in listing
    assert _cli(["jobs", "status", fd, "ok"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["state"] == "done"
    assert rec["report"]["steps"]["completed"] == 4
    assert _cli(["jobs", "status", fd, "nope"]) == 3
    capsys.readouterr()
    # control requests: unknown -> 3, finished -> 4, drain files its
    # request for a live scheduler to consume
    assert _cli(["jobs", "cancel", fd, "nope"]) == 3
    assert _cli(["jobs", "cancel", fd, "ok"]) == 4
    assert _cli(["jobs", "drain", fd]) == 0
    capsys.readouterr()
    import os

    assert os.path.exists(os.path.join(fd, "control", "drain"))
    # queue JSON validation: a typo'd/misplaced knob must fail loudly,
    # never silently run with defaults
    from implicitglobalgrid_tpu.utils.exceptions import (
        InvalidArgumentError,
    )

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"jobs": [
        {"name": "x", "model": "diffusion3d", "nt": 4, "nt_chunk": 2}]}))
    with pytest.raises(InvalidArgumentError, match="unknown key"):
        _cli(["jobs", "submit", str(bad)])
    bad.write_text(json.dumps({"jobs": [{"name": "x", "nt": 4}]}))
    with pytest.raises(InvalidArgumentError, match="missing required"):
        _cli(["jobs", "submit", str(bad)])


def test_layout_override_coordinate_helpers():
    """x_g must honor layout= for the same ambiguous block the nx_g test
    documents: a (8,4,4) LOCAL block on a dims=(2,1,1) grid reads as stacked
    by default (divmod over the inferred shard), but layout='local' +
    explicit coords gives the true local-block coordinates."""
    igg.init_global_grid(4, 4, 4, dimx=2, dimy=1, dimz=1, quiet=True)
    A = np.zeros((8, 4, 4))
    # default inference: stacked -> ix=5 is shard 1, local 1 -> (1*(4-2)+1)
    assert igg.x_g(5, 1.0, A) == 1 * (4 - 2) + 1
    # forced local reading on shard 0: ix=5 is local index 5 of a staggered
    # block (x0 offset = 0.5*(4-8)*dx = -2)
    assert igg.x_g(5, 1.0, A, coords=0, layout="local") == 5 - 2.0
    v = igg.x_g_vec(1.0, A, layout="local")
    assert v.shape[0] == 2 * 8  # stacked vector over the local size
