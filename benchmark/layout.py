"""Where each cell of the program's stacked arrays lies on the global grid.

The program keeps a field as one "stacked" array: the local blocks of all
shards side by side, each block with its halo cells (overlap 2, one halo
cell per side). On a periodic axis with ``dims`` shards of ``n`` local
cells the global grid has ``N = dims * (n - 2)`` cells, and local index
``l`` of shard ``c`` is global cell ``(c * (n - 2) + l - 1) mod N``. A
face-staggered field has ``n + 1`` local entries along its staggered axis;
local face ``l`` lies between local cells ``l - 1`` and ``l``, so the same
formula gives its global face (face ``f`` lies between cells ``f - 1`` and
``f``), and the global grid has ``N`` faces.

The benchmark builds its fields from these maps and reads the program's
answers through them, so it needs nothing of the program to do either."""

from __future__ import annotations

import numpy as np

OVERLAP = 2


def axis_map(local_len: int, n: int, dims: int):
    """Global index of every stacked index along one periodic axis."""
    N = dims * (n - OVERLAP)
    c, l = np.divmod(np.arange(dims * local_len), local_len)
    return (c * (n - OVERLAP) + l - 1) % N


def owned(local_len: int, n: int, dims: int):
    """Stacked indices that own global indices ``0 .. N-1``, in that order:
    each shard's local ``1 .. n-2`` (cells and faces alike)."""
    return np.concatenate([c * local_len + np.arange(1, n - 1)
                           for c in range(dims)])


class Layout:
    """The stacked layout of one cell's fields: local cell counts ``n``
    and shard counts ``dims`` per axis, and each field's stagger."""

    def __init__(self, n, dims, staggers: dict):
        self.n = tuple(int(v) for v in n)
        self.dims = tuple(int(v) for v in dims)
        self.staggers = {k: tuple(int(s) for s in v)
                         for k, v in staggers.items()}
        self.global_shape = tuple(d * (m - OVERLAP)
                                  for m, d in zip(self.n, self.dims))

    def local_shape(self, name):
        return tuple(m + s for m, s in zip(self.n, self.staggers[name]))

    def maps(self, name):
        return [axis_map(L, m, d) for L, m, d in
                zip(self.local_shape(name), self.n, self.dims)]

    def to_global(self, name, stacked, xp=np):
        """The global field, each value taken from its owning shard
        (``xp``: `numpy`, or `jax.numpy` for a device array)."""
        for a, (L, m, d) in enumerate(zip(self.local_shape(name), self.n,
                                          self.dims)):
            stacked = xp.take(stacked, xp.asarray(owned(L, m, d)), axis=a)
        return stacked

    def to_stacked(self, name, glob, xp=np):
        """The stacked array whose every cell, halos included, holds the
        global field's value at its global index."""
        for a, m in enumerate(self.maps(name)):
            glob = xp.take(glob, xp.asarray(m), axis=a)
        return glob


def _fmix32(h, u32):
    """MurmurHash3's 32-bit finalizer, on uint32 numpy (``u32`` =
    ``np.uint32``) or jax (``jnp.uint32``) arrays; products wrap."""
    h = h ^ (h >> u32(16))
    h = h * u32(0x85EBCA6B)
    h = h ^ (h >> u32(13))
    h = h * u32(0xC2B2AE35)
    return h ^ (h >> u32(16))


def field_key(seed: int, salt: int) -> int:
    """A 32-bit key from any whole ``seed`` (all its bits) and a field's
    ``salt``."""
    seed = int(seed) % (1 << 64)
    k = np.uint32(salt * 0x632BE5AB & 0xFFFFFFFF)
    with np.errstate(over="ignore"):
        for part in (seed & 0xFFFFFFFF, seed >> 32):
            k = _fmix32(np.uint32(k ^ np.uint32(part))
                        * np.uint32(0x9E3779B1) + np.uint32(0x7F4A7C15),
                        np.uint32)
    return int(k)


def seeded_state(layout: Layout, fields: dict, seed: int, dtype, sharding):
    """The stacked fields ``{name: lo + span * u}`` for ``fields = {name:
    (lo, span)}``, ``u`` uniform in [0, 1) hashed from the seed, the
    field's position in ``fields`` and each cell's GLOBAL index, so halos
    agree with their partners. One jitted call for all fields;
    ``sharding`` places each shard's block on its device, where it is
    computed. The keys are arguments, so one compiled program serves every
    seed."""
    import jax
    import jax.numpy as jnp

    def one(name, k, lo, span):
        mx, my, mz = (jnp.asarray(np.asarray(m, np.uint32))
                      for m in layout.maps(name))
        _, NY, NZ = (np.uint32(v) for v in layout.global_shape)
        lin = ((mx[:, None, None] * NY + my[None, :, None]) * NZ
               + mz[None, None, :])
        h = _fmix32(lin * jnp.uint32(0x9E3779B1) + k, jnp.uint32)
        u = (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
        return (jnp.float32(lo) + jnp.float32(span) * u).astype(dtype)

    def gen(keys):
        return {name: one(name, keys[i], lo, span)
                for i, (name, (lo, span)) in enumerate(fields.items())}

    keys = np.array([field_key(seed, i + 1) for i in range(len(fields))],
                    np.uint32)
    return jax.jit(gen, out_shardings=sharding)(jnp.asarray(keys))
