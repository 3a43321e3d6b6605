"""Plain reference of the served BlockAMC solve, written from the paper.

BlockAMC (arXiv:2401.10042): the matrix is normalised by c = 1/max|A|,
split as [[A1, A2], [A3, A4]] with A1 taking ceil(n/2) rows, the Schur
complement A4s = A4 - A3 A1^-1 A2 is computed digitally, and each stage
recurses on A1 and A4s until `stages` are spent.  Each block is programmed
as a differential pair of conductance arrays, G+ = max(cA, 0) G0 and
G- = max(-cA, 0) G0, each with additive Gaussian write noise sigma G0
clipped at 0; MVM blocks wider than the array are tiled.  Readout applies
first-order wire resistance, G_eff = G - r [G .* (C G) + G .* (G S)] with
C[i,i'] = 1 + min(i,i') and S[j,j'] = cols - max(j,j').  The cascade is
Algorithm 1 with the circuits' signs: INV gives -A_eff^-1 v, MVM gives
-A_eff v, and x = -c * (the root INV of b).

Noise draws follow the key discipline the configuration states: a block
node splits its key four ways (A1, A2, A3, A4s), a tiled MVM splits its
key once per tile in row-major order, and each array pair splits its key
into (G+, G-), each drawing one standard normal per device in float32
with `jax.random.normal` on the default device, as the served program
does: on a TPU v5e the float32 normals differ from the CPU's by up to
2.6e-5 relative, more than the float32 rounding the comparison has to
see.

The reference imports nothing of the program under test.  It is written
once over an array backend: `REFERENCE` runs it in float64 numpy on the
host; `CONTROL` runs it in float32 with every dot as three bf16 passes
(the precision one step below the stated HIGHEST), on the default device.
Leading axes batch draws: A is (B or 1, n, n), keys (B, 2).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    xp: object
    dtype: object
    matmul: Callable
    solve: Callable
    device: Callable      # -> the jax device noise is drawn on


def _dot_high(a, b):
    """float32 dot as three bf16 passes (hi*hi + hi*lo + lo*hi), as XLA's
    `Precision.HIGH` computes it, spelled out so that it reads the same on
    every backend."""
    def split(x):
        hi = x.astype(jnp.bfloat16)
        lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi, lo

    (ah, al), (bh, bl) = split(a), split(b)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


REFERENCE = Backend("float64", np, np.float64, np.matmul, np.linalg.solve,
                    lambda: jax.devices()[0])
CONTROL = Backend("float32-high", jnp, jnp.float32, _dot_high,
                  jnp.linalg.solve, lambda: jax.devices()[0])


def split_tree(n: int, stages: int):
    if stages == 0 or n <= 1:
        return int(n)
    m = -(-n // 2)
    return (split_tree(m, stages - 1), split_tree(n - m, stages - 1))


def _size(tree) -> int:
    return tree if isinstance(tree, int) else _size(tree[0]) + _size(tree[1])


class _Noise:
    """float32 standard normals and key splits, batched over keys."""

    def __init__(self, be: Backend):
        self.be = be

    def split(self, keys, num):
        with jax.default_device(self.be.device()):
            return jax.vmap(lambda k: jax.random.split(k, num))(
                jnp.asarray(keys))

    def normal(self, keys, shape):
        with jax.default_device(self.be.device()):
            z = jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(
                jnp.asarray(keys))
        if self.be.xp is np:
            return np.asarray(z).astype(self.be.dtype)
        return z.astype(self.be.dtype)


def _wire(g, r, be):
    if r == 0.0:
        return g
    xp = be.xp
    rows, cols = g.shape[-2:]
    i = xp.arange(rows, dtype=be.dtype)
    j = xp.arange(cols, dtype=be.dtype)
    c_bl = 1.0 + xp.minimum(i[:, None], i[None, :])
    s_wl = cols - xp.maximum(j[:, None], j[None, :])
    return g - r * (g * be.matmul(c_bl, g) + g * be.matmul(g, s_wl))


def _array(block, keys, scale, cfg, be, noise):
    """One programmed differential pair, read out: A_eff (B, r, c)."""
    xp = be.xp
    g0, sg = cfg["g0"], cfg["sigma"] * cfg["g0"]
    a = block * scale[:, None, None]
    gp, gn = xp.maximum(a, 0.0) * g0, xp.maximum(-a, 0.0) * g0
    if sg:
        kp_kn = noise.split(keys, 2)
        shape = tuple(block.shape[-2:])
        gp = xp.maximum(gp + sg * noise.normal(kp_kn[:, 0], shape), 0.0)
        gn = xp.maximum(gn + sg * noise.normal(kp_kn[:, 1], shape), 0.0)
    r = cfg["r_wire"]
    return (_wire(gp, r, be) - _wire(gn, r, be)) / g0


def _tiled(block, keys, scale, cfg, be, noise):
    s = cfg["array_size"]
    rows, cols = block.shape[-2:]
    rt, ct = -(-rows // s), -(-cols // s)
    tk = noise.split(keys, rt * ct)
    return [[(c0, _array(block[..., r0:r0 + s, c0:c0 + s],
                         tk[:, ri * ct + ci], scale, cfg, be, noise))
             for ci, c0 in enumerate(range(0, cols, s))]
            for ri, r0 in enumerate(range(0, rows, s))]


def _program(a, tree, keys, scale, cfg, be, noise):
    if isinstance(tree, int):
        return _array(a, keys, scale, cfg, be, noise)
    m = _size(tree[0])
    a1, a2 = a[..., :m, :m], a[..., :m, m:]
    a3, a4 = a[..., m:, :m], a[..., m:, m:]
    a4s = a4 - be.matmul(a3, be.solve(a1, a2))
    k = noise.split(keys, 4)
    return (m,
            _program(a1, tree[0], k[:, 0], scale, cfg, be, noise),
            _tiled(a2, k[:, 1], scale, cfg, be, noise),
            _tiled(a3, k[:, 2], scale, cfg, be, noise),
            _program(a4s, tree[1], k[:, 3], scale, cfg, be, noise))


def _mvm(tiles, v, be):
    rows = []
    for row in tiles:
        acc = None
        for c0, w in row:
            part = -be.matmul(w, v[..., c0:c0 + w.shape[-1], :])
            acc = part if acc is None else acc + part
        rows.append(acc)
    return be.xp.concatenate(rows, axis=-2)


def _inv(node, v, be):
    """-A^-1 v through Algorithm 1 (circuit signs kept)."""
    if not isinstance(node, tuple):
        return -be.solve(node, v)
    m, inv1, mvm2, mvm3, inv4s = node
    f, g = v[..., :m, :], v[..., m:, :]
    neg_yt = _inv(inv1, f, be)                 # step 1
    gt = _mvm(mvm3, neg_yt, be)                # step 2
    z = _inv(inv4s, -g + gt, be)               # step 3
    neg_ft = _mvm(mvm2, z, be)                 # step 4
    neg_y = _inv(inv1, f + neg_ft, be)         # step 5
    return be.xp.concatenate([neg_y, -z], axis=-2)


def solve(cfg: dict, a, keys, b, be: Backend = REFERENCE):
    """Program `a` under each of `keys` and solve for `b`.

    a: (1 or B, n, n); keys: (B, 2) uint32; b: (1 or B, n, k).
    Returns (B, n, k) in the backend's dtype."""
    xp = be.xp
    keys = np.asarray(keys)
    nb = keys.shape[0]
    a = xp.asarray(a, dtype=be.dtype)
    b = xp.asarray(b, dtype=be.dtype)
    scale = 1.0 / xp.max(xp.abs(a), axis=(-2, -1))
    scale = xp.broadcast_to(scale, (nb,))
    b = xp.broadcast_to(b, (nb,) + b.shape[1:]) if b.shape[0] != nb else b
    noise = _Noise(be)
    root = _program(a, split_tree(a.shape[-1], cfg["stages"]), keys, scale,
                    cfg, be, noise)
    return -scale[:, None, None] * _inv(root, b, be)
