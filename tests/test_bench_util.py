"""bench_util: the emit/device-tagging contract, the two-point
steady-state measurement (the method every bench rate flows through), the
supervision layer (pid-stamped child marker; a failed or TPU-less child
fails the run), and the compile-cache placement every entry point uses."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import bench_util  # noqa: E402


def test_emit_tags_device_fields(capsys):
    row = bench_util.emit({"metric": "m", "value": 1.0, "unit": "u"})
    out = capsys.readouterr().out
    assert row["platform"] == "cpu" and row["n_devices"] >= 1
    assert '"metric": "m"' in out


def test_two_point_slope_and_fallback():
    calls = []

    def chunk(c):
        calls.append(c)

    def fake_timer(cost):
        # timer(fn) runs fn (one chunk call) and reports a deterministic
        # wall time for it — no real sleeps, so nothing to flake on.
        def timer(fn):
            fn()
            return cost(calls[-1])

        return timer

    # fixed 20ms/call + 4ms/step: the slope recovers exactly the per-step
    # cost, NOT the fixed part
    s = bench_util.two_point(chunk, 5, 15, reps=1,
                             timer=fake_timer(lambda c: 0.02 + 0.004 * c))
    assert abs(s - 0.004) < 1e-12, s
    assert bench_util.two_point.last["method"] == "two-point"
    # warms both windows, then one timed run each
    assert calls == [5, 15, 5, 15]

    # flat per-call time (t2 == t1) → inclusive big-window fallback, and
    # the .last record says so (ADVICE r3: emitted rows must be able to
    # distinguish the two semantics)
    calls.clear()
    s2 = bench_util.two_point(chunk, 5, 15, reps=1,
                              timer=fake_timer(lambda c: 0.01))
    assert abs(s2 - 0.01 / 15) < 1e-12
    assert bench_util.two_point.last["method"] == "inclusive-fallback"


def test_is_child_rejects_leaked_marker(monkeypatch):
    # round-3 failure mode: IGG_BENCH_CHILD present in the invoking
    # environment must NOT route the script down the unsupervised path —
    # not even "1" in a container where the parent IS pid 1
    monkeypatch.setenv("IGG_BENCH_CHILD", "1")
    assert not bench_util.is_child()
    monkeypatch.setenv("IGG_BENCH_CHILD", str(os.getppid()))
    assert not bench_util.is_child()  # pid alone is not enough
    # the real marker: supervising parent's pid + random token
    monkeypatch.setenv("IGG_BENCH_CHILD",
                       f"{os.getppid()}:deadbeefdeadbeef")
    assert bench_util.is_child()
    monkeypatch.delenv("IGG_BENCH_CHILD")
    assert not bench_util.is_child()


@pytest.mark.parametrize("cpu", [True, False], ids=["cpu-child-fails",
                                                   "no-tpu"])
def test_supervised_failure_exits_nonzero(tmp_path, cpu):
    """A child whose measurement raises (``--cpu``), or that finds no TPU
    (no ``--cpu`` on this CPU-only box), makes the supervised run exit 1
    with one error row: no fallback, no number."""
    script = tmp_path / "fake_bench.py"
    script.write_text(textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(REPO)!r})
        import bench_util

        def main():
            print(json.dumps({{"metric": "m", "value": 1.0}}))
            raise RuntimeError("measurement failed")

        bench_util.run(main, "m", "u")
    """))
    env = {k: v for k, v in os.environ.items() if k != "IGG_BENCH_CHILD"}
    proc = subprocess.run([sys.executable, str(script)]
                          + (["--cpu"] if cpu else []),
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 1
    rows = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.strip().startswith("{")]
    assert rows == [{"metric": "m", "value": None, "unit": "u",
                     "error": rows[0]["error"]}]
    assert ("measurement failed" if cpu else "no TPU") in rows[0]["error"]


@pytest.mark.parametrize("env_dir", ["/somewhere/jax-cache", None],
                         ids=["env-set", "env-unset"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code. Unset:
    the fixed in-checkout path."""
    import jax

    from implicitglobalgrid_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    got = compile_cache.use_compile_cache()
    if env_dir is None:
        want = str(REPO / ".jax_cache")
        assert got == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        assert got == env_dir and updates == []
