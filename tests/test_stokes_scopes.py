"""The device-side name scopes of the fused PT Stokes step
(`ops/pallas_stokes.SCOPES`): each reaches the lowered program's op
metadata, on one shard (the all-self path) and on a 2x2x1 mesh (the packed
exchange), and they change nothing else in the program."""

import contextlib

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg


def _lowered(dims, monkeypatch, scoped):
    import jax

    from implicitglobalgrid_tpu.models import init_stokes3d, stokes_step_local
    from implicitglobalgrid_tpu.ops import pallas_stokes
    from implicitglobalgrid_tpu.ops.fields import field_partition_spec

    if not scoped:
        monkeypatch.setattr(pallas_stokes, "_scope",
                            lambda part: contextlib.nullcontext())
    igg.init_global_grid(10, 9, 8, dimx=dims[0], dimy=dims[1], dimz=dims[2],
                         periodx=1, periody=1, periodz=1, quiet=True)
    state, p = init_stokes3d(dtype=np.float32)
    specs = tuple(field_partition_spec(3) for _ in state)
    fn = jax.jit(jax.shard_map(
        lambda *s: stokes_step_local(s, p, "pallas_interpret"),
        mesh=igg.global_grid().mesh, in_specs=specs, out_specs=specs,
        check_vma=False))
    low = fn.lower(*state)
    igg.finalize_global_grid()
    return low.as_text(), low.as_text(debug_info=True)


@pytest.mark.parametrize("dims", [(1, 1, 1), (2, 2, 1)],
                         ids=["1x1x1", "2x2x1"])
def test_scopes_reach_the_op_metadata_and_change_nothing_else(dims,
                                                              monkeypatch):
    from implicitglobalgrid_tpu.ops.pallas_stokes import SCOPES

    text, debug = _lowered(dims, monkeypatch, scoped=True)
    for scope in SCOPES.values():
        assert f'"{scope}/' in debug or f"/{scope}/" in debug, scope
    plain, plain_debug = _lowered(dims, monkeypatch, scoped=False)
    assert not any(s in plain_debug for s in SCOPES.values())
    for op in ("custom_call", "collective_permute"):
        assert text.count(op) == plain.count(op)
    assert ("collective_permute" in text) == (dims != (1, 1, 1))
    assert text == plain
