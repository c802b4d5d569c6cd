"""The hot-set partition (dintcache): the engines keep a compact physical
mirror of the hot index prefix (engines/smallbank_dense.attach_hotset)
that installs write through to, so there is no coherence protocol, just a
partition. Lanes with ``midx >= 0`` are served from the mirror, the rest
from the table: bit-identical to the plain gather / scatter whenever the
mirror invariant ``mirror[m] == tab[row_of(m)]`` holds, which the
write-through installs maintain by construction."""
from __future__ import annotations

import os

import jax.numpy as jnp

I32 = jnp.int32


def env_use_hotset() -> bool:
    return os.environ.get("DINT_USE_HOTSET", "0") not in ("", "0")


def resolve_use_hotset(explicit: bool | None = None) -> bool:
    """Engine-builder gate for the hot-set partition: explicit kwarg wins,
    else the DINT_USE_HOTSET env."""
    if explicit is None:
        return env_use_hotset()
    return bool(explicit)


def hot_gather(tab, mirror, idx, midx, vw: int = 1):
    """The partitioned gather: index-compare + small-array gather.
    ``out[i] = mirror[midx[i]] if midx[i] >= 0 else tab[idx[i]]`` (rows of
    vw words). Returns u32 [K*vw]."""
    idx, midx = idx.astype(I32), midx.astype(I32)
    flat_c = (idx[:, None] * vw + jnp.arange(vw, dtype=I32)).reshape(-1)
    mc = jnp.maximum(midx, 0)
    flat_h = (mc[:, None] * vw + jnp.arange(vw, dtype=I32)).reshape(-1)
    hot = jnp.repeat(midx >= 0, vw)
    return jnp.where(hot, mirror[flat_h], tab[flat_c])


def hot_scatter(tab, mirror, idx, midx, mask, vals, vw: int = 1):
    """The write-through install: each masked lane's row goes into the
    table AND (for ``midx >= 0`` lanes) into the mirror, a double scatter
    (both 1-D unique-index fast paths). Masked-out lanes write nothing;
    indices among masked lanes must be unique, the one-X-writer-per-row
    contract the engines' ``unique_indices=True`` scatters already
    certify. Returns (tab', mirror')."""
    n_tab = tab.shape[0] // vw
    n_mir = mirror.shape[0] // vw
    widx = jnp.where(mask != 0, idx, n_tab)
    wflat = (widx[:, None] * vw + jnp.arange(vw, dtype=I32)).reshape(-1)
    tab = tab.at[wflat].set(vals, mode="drop", unique_indices=True)
    hmask = (mask != 0) & (midx >= 0)
    hidx = jnp.where(hmask, midx, n_mir)
    hflat = (hidx[:, None] * vw + jnp.arange(vw, dtype=I32)).reshape(-1)
    mirror = mirror.at[hflat].set(vals, mode="drop", unique_indices=True)
    return tab, mirror
