"""Device and circuit non-ideality models (paper Section IV).

Two non-idealities are studied by the paper:

1. Conductance variation: each programmed RRAM conductance deviates from its
   target by additive Gaussian noise with sigma = 0.05 * G0 (write&verify
   limit, refs [6], [20]).  Applied independently per device, per array.

2. Interconnect (wire) resistance: 1 ohm per segment between adjacent cells
   along a bit-line or word-line (65 nm node, ref [12]).  The paper simulates
   the full circuit in HSPICE; here we provide
     * a first-order effective-conductance model (fast, O(n^2), used at all
       sizes) following the standard IR-drop approximation (Chen ICCAD'15,
       Luo TCAS-I'22 - both cited by the paper), and
     * an exact Modified-Nodal-Analysis (MNA) solver of the full crossbar
       (dense, used for validation at small n; this plays HSPICE's role).

Geometry convention (fixed; documented in DESIGN.md): input drivers sit at
row 0 of each bit-line; the sensing amplifier (TIA virtual ground for the MVM
circuit, OPA summing node for the INV circuit) sits at the last column of
each word-line.  Current through cell (i, j) therefore traverses ~ (i + 1)
BL segments and ~ (n_cols - j) WL segments.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.precision import F32_DOT


# ---------------------------------------------------------------------------
# Conductance variation
# ---------------------------------------------------------------------------

def apply_variation(g: jnp.ndarray, key: jax.Array, sigma_g: float) -> jnp.ndarray:
    """Additive Gaussian conductance noise, clipped at zero (physical)."""
    if sigma_g == 0.0:
        return g
    noise = sigma_g * jax.random.normal(key, g.shape, dtype=g.dtype)
    return jnp.maximum(g + noise, 0.0)


# ---------------------------------------------------------------------------
# First-order interconnect-resistance model
# ---------------------------------------------------------------------------

def effective_conductance(g: jnp.ndarray, r_seg: float) -> jnp.ndarray:
    """First-order (in r*G) effective conductance matrix of a wired crossbar.

    Perturbation with the *true* current distribution: the IR drop seen by
    cell (i, j) is a linear functional of all cell currents that share wire
    segments with its path.  With the driver at row 0 of each bit line and
    the sense node past the last column of each word line,

      shared BL segments of cells (i, j) and (i', j):  1 + min(i, i')
      shared WL segments of cells (i, j) and (i, j'):  n_c - max(j, j')

    giving (elementwise products with the segment-count kernels C, S):

      G_eff = G - r * [ G .* (C @ G) + G .* (G @ S) ],
      C[i, i'] = 1 + min(i, i'),   S[j, j'] = n_c - max(j, j').

    Exact to O((r G n)^2); validated against the exact MNA oracle in tests.
    Cost is two n x n matmuls - free at crossbar sizes.

    `r_seg` may be a traced scalar (the model is linear in r_seg, so it is
    differentiable - the calibration path); the zero-resistance early-out
    only fires for static Python zeros.
    """
    if isinstance(r_seg, (int, float)) and r_seg == 0.0:
        return g
    return g - r_seg * _ir_drop(g)


def _ir_drop(g: jnp.ndarray) -> jnp.ndarray:
    """G .* (C @ G) + G .* (G @ S): the first-order IR-drop term per unit
    segment resistance (see `effective_conductance`), both dots at F32_DOT."""
    n_rows, n_cols = g.shape
    dtype = g.dtype
    i = jnp.arange(n_rows, dtype=dtype)
    j = jnp.arange(n_cols, dtype=dtype)
    c_bl = 1.0 + jnp.minimum(i[:, None], i[None, :])
    s_wl = n_cols - jnp.maximum(j[:, None], j[None, :])
    return (g * jnp.matmul(c_bl, g, precision=F32_DOT)
            + g * jnp.matmul(g, s_wl, precision=F32_DOT))


def compensate_conductances(g_target: jnp.ndarray, r_seg: float,
                            iters: int = 3) -> jnp.ndarray:
    """Write-verify compensation for wire IR drop (paper ref [29], Luo et al.
    TCAS-I'22: program conductances such that the *effective* matrix equals
    the target).

    Solves G_eff(G_prog) = G_target by fixed-point iteration on the
    linearised model: G_prog <- G_target + r * drop(G_prog).  Converges in
    2-3 iterations in the r*G*n << 1 regime (the paper's operating point).
    Physical constraint: programmed conductances must stay non-negative.
    """
    if r_seg == 0.0:
        return g_target
    g = g_target
    for _ in range(iters):
        g = jnp.maximum(g_target + r_seg * _ir_drop(g), 0.0)
    return g


# ---------------------------------------------------------------------------
# Exact MNA crossbar solvers (validation oracles, small n; HSPICE stand-in)
# ---------------------------------------------------------------------------
#
# Node layout for an (nr x nc) crossbar with wire segments:
#   BL node b(i,j): on bit-line (column) j at row i       -> index i*nc + j
#   WL node w(i,j): on word-line (row) i at column j      -> index nr*nc + i*nc + j
# Cell (i,j) connects b(i,j) <-> w(i,j) with conductance g[i,j].
# BL segments connect b(i-1,j) <-> b(i,j); the driver feeds b(0,j) through
# one segment.  WL segments connect w(i,j) <-> w(i,j+1); the sense node is
# one segment past w(i, nc-1) and is held at virtual ground.


def _crossbar_laplacian(g, r_seg: float):
    """Build the (2*nr*nc) x (2*nr*nc) conductance Laplacian plus the
    driver/sense coupling matrices.  Dense numpy (validation oracle only)."""
    import numpy as np
    g = np.asarray(g, dtype=np.float64)
    nr, nc = g.shape
    n_nodes = 2 * nr * nc
    gw = 1.0 / r_seg

    bl = (np.arange(nr)[:, None] * nc + np.arange(nc)[None, :])
    wl = nr * nc + bl

    L = np.zeros((n_nodes, n_nodes))

    def stamp(a_idx, b_idx, cond):
        a_idx = np.asarray(a_idx)
        cond = np.broadcast_to(np.asarray(cond, dtype=np.float64), a_idx.shape)
        a_idx = a_idx.ravel()
        b_idx = np.asarray(b_idx).ravel()
        cond = cond.ravel()
        np.add.at(L, (a_idx, a_idx), cond)
        np.add.at(L, (b_idx, b_idx), cond)
        np.add.at(L, (a_idx, b_idx), -cond)
        np.add.at(L, (b_idx, a_idx), -cond)

    stamp(bl, wl, g)                         # cells
    stamp(bl[:-1, :], bl[1:, :], gw)         # BL wire segments (vertical)
    stamp(wl[:, :-1], wl[:, 1:], gw)         # WL wire segments (horizontal)
    # Driver coupling: v_in[j] -> b(0,j) through one BL segment.
    drive = np.zeros((n_nodes, nc))
    np.add.at(L, (bl[0, :], bl[0, :]), gw)
    drive[bl[0, :], np.arange(nc)] = gw
    # Sense coupling: w(i, nc-1) -> virtual ground through one WL segment.
    sense = np.zeros((n_nodes, nr))
    np.add.at(L, (wl[:, -1], wl[:, -1]), gw)
    sense[wl[:, -1], np.arange(nr)] = gw
    return L, drive, sense


def mna_mvm_currents(g, v_in, r_seg: float):
    """Exact sense currents of the MVM crossbar (TIA inputs at 0 V).

    Returns I[i], the current flowing into the virtual ground of row i.
    Ideal limit (r_seg -> 0): I = g @ v_in.  Numpy float64 oracle: the
    return value is a float64 numpy array regardless of jax's x64 mode
    (a `jnp.asarray` here used to truncate the oracle to f32).
    """
    import numpy as np
    L, drive, sense = _crossbar_laplacian(g, r_seg)
    v_in = np.asarray(v_in, dtype=np.float64)
    # KCL at all internal nodes: L v = drive @ v_in   (sense nodes at 0 V are
    # already folded into L's diagonal via the sense coupling).
    v = np.linalg.solve(L, drive @ v_in)
    # Current into each virtual ground = gw * v(w(i, nc-1)).
    return sense.T @ v


def mna_inv_outputs(g, v_in, r_seg: float, g0: float):
    """Exact OPA output voltages of the INV circuit with wire resistance.
    Returns a float64 numpy array (full-precision oracle, like
    `mna_mvm_currents`).

    Circuit (paper Fig. 1b): v_in[i] injected through a G0 resistor into word
    line i's summing node; OPA i senses that node (ideal virtual ground) and
    drives bit line i.  Feedback through the crossbar enforces
        G0 v_in + G_eff v_out = 0   =>   v_out = -(G_eff/G0)^-1 v_in.

    Unknowns: internal node voltages v (2*nr*nc) and OPA outputs u (nc).
    Equations: KCL at every internal node, plus n 'summing node at 0 V'
    constraints.  The summing node of row i is the sense node (one WL segment
    past w(i, nc-1)); it receives gw*(w(i,nc-1) - 0) + g0*(v_in[i] - 0) and
    sources the OPA input current (ideal OPA: zero), so KCL there is the
    constraint row.
    """
    import numpy as np
    nr, nc = g.shape
    assert nr == nc, "INV circuit requires a square array"
    L, drive, sense = _crossbar_laplacian(g, r_seg)
    v_in = np.asarray(v_in, dtype=np.float64)
    n_nodes = 2 * nr * nc
    # OPA outputs u drive the BLs where v_in drove them in MVM mode.
    #   KCL at internal nodes:  L v - drive @ u = 0.
    #   Summing-node constraint (ideal OPA, node at 0 V, no input current):
    #   array current into the node + G0 input branch current = 0:
    #       (sense.T @ v)[i] + g0 * v_in[i] = 0.
    top = np.concatenate([L, -drive], axis=1)
    bot = np.concatenate([sense.T, np.zeros((nr, nc))], axis=1)
    M = np.concatenate([top, bot], axis=0)
    rhs = np.concatenate([np.zeros((n_nodes,)), -g0 * v_in])
    sol = np.linalg.solve(M, rhs)
    return sol[n_nodes:]


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NonidealConfig:
    """Knobs for the analog non-ideality models (paper Section IV defaults).

    All fields are static Python scalars: the config is hashed into
    `plan_signature`, so any field combination is a distinct compile/packing
    key and new fields flow into the packed-serving stackability rule
    automatically.

    Wire model dispatch: "first_order" is the O(n^2) perturbation used on
    the hot path; "nodal" routes readout through the exact batched MNA
    solver in `repro.physics.nodal` (block-tridiagonal, jit/vmap-safe);
    "none" disables the wire model even when r_wire > 0.

    Device dynamics (physics subsystem):
      * drift_t / drift_nu: power-law retention drift G(t) = G (t/t0)^-nu
        with t0 = 1 s, applied at readout time (`readout_conductance`).
      * p_stuck_on / p_stuck_off: per-device stuck-at fault rates applied at
        programming time; stuck cells read g_stuck_{on,off} * G0 regardless
        of target.  `remap_faults` enables target-aware row/column remapping
        (repro.physics.faults) that steers faults onto low-impact entries.
      * compensate_model: which wire model write-verify tracks
        (None = same as `wire_model`); `wv_iters` is the fixed-point depth.
    """
    sigma: float = 0.0        # conductance sigma in units of G0 (paper: 0.05)
    r_wire: float = 0.0       # wire segment resistance in ohms (paper: 1.0)
    wire_model: str = "first_order"   # "first_order" | "nodal" | "none"
    compensate_wire: bool = False     # write-verify IR-drop compensation
    # (paper ref [29] mitigation; applied at programming time in map_matrix)
    compensate_model: Optional[str] = None  # None -> wire_model
    wv_iters: int = 3                 # write-verify fixed-point iterations
    drift_t: float = 0.0              # readout time since programming [s]
    drift_nu: float = 0.0             # power-law drift exponent (0 = off)
    p_stuck_on: float = 0.0           # fraction of devices stuck at G_on
    p_stuck_off: float = 0.0          # fraction of devices stuck at G_off
    g_stuck_on: float = 1.0           # stuck-ON conductance, units of G0
    g_stuck_off: float = 0.0          # stuck-OFF conductance, units of G0
    remap_faults: bool = False        # fault-aware row/column remapping

    VARIATION_PAPER = 0.05
    R_WIRE_PAPER = 1.0


IDEAL = NonidealConfig()
PAPER_VARIATION = NonidealConfig(sigma=0.05)
PAPER_FULL = NonidealConfig(sigma=0.05, r_wire=1.0)


# ---------------------------------------------------------------------------
# Shared programming / readout pipeline
# ---------------------------------------------------------------------------
#
# Everything the config can express funnels through exactly two functions:
#
#   program_conductances : target -> device state   (write-verify, write
#                          noise, stuck-at faults; programming time)
#   readout_conductance + wire_readout : device state -> matrix the circuit
#                          computes with (drift, then the wire model;
#                          readout time, called from {CrossbarPair,
#                          TileGrid}.a_eff)
#
# so all four executors (recursive / flat / finalized / fused-arena) and the
# packed-serving layer see identical physics without any changes of their
# own.  The physics subsystem (repro.physics) is imported lazily so the core
# package has no hard dependency on it at import time.

def _over_tiles(fn, g: jnp.ndarray) -> jnp.ndarray:
    """Apply a 2-D (r, c) -> (r, c) map over arbitrary leading batch axes."""
    lead = g.shape[:-2]
    if not lead:
        return fn(g)
    flat = g.reshape((-1,) + g.shape[-2:])
    return jax.vmap(fn)(flat).reshape(g.shape)


def program_conductances(g_target: jnp.ndarray, key: jax.Array,
                         ni: NonidealConfig, g0: float) -> jnp.ndarray:
    """The one programming pipeline: write-verify -> write noise -> faults.

    `g_target` is a (..., r, c) stack of target conductances (one physical
    array per trailing 2-D slice; leading axes are tile/batch axes).
    Deterministic write-verify pre-distortion happens against the configured
    wire model; Gaussian write noise and stuck-at faults are drawn from
    `key` independently per device.
    """
    g = g_target
    if ni.compensate_wire and ni.r_wire > 0.0:
        model = ni.compensate_model or ni.wire_model
        if model == "first_order":
            g = _over_tiles(
                partial(compensate_conductances, r_seg=ni.r_wire,
                        iters=ni.wv_iters), g)
        elif model == "nodal":
            from repro.physics import dynamics as _dyn
            g = _over_tiles(
                partial(_dyn.write_verify, r_seg=ni.r_wire, model="nodal",
                        iters=ni.wv_iters), g)
        elif model != "none":
            raise ValueError(f"unknown compensate_model: {model!r}")
    # Key discipline: with faults off, variation consumes `key` directly so
    # seeded noise realizations are bit-identical to the pre-physics pipeline.
    has_faults = ni.p_stuck_on > 0.0 or ni.p_stuck_off > 0.0
    k_var, k_fault = jax.random.split(key) if has_faults else (key, key)
    g = apply_variation(g, k_var, ni.sigma * g0)
    if has_faults:
        from repro.physics import faults as _faults
        g = _faults.apply_stuck_faults(
            g, g_target, k_fault, p_on=ni.p_stuck_on, p_off=ni.p_stuck_off,
            g_on=ni.g_stuck_on * g0, g_off=ni.g_stuck_off * g0,
            remap=ni.remap_faults)
    return g


def readout_conductance(g: jnp.ndarray, ni: NonidealConfig,
                        drift_t=None) -> jnp.ndarray:
    """Device state at readout time: power-law retention drift.

    G(t) = G(t0) * (t/t0)^-nu with t0 = 1 s; `drift_t`/`drift_nu` are static
    config floats, so the no-drift case costs nothing at trace time.

    `drift_t` optionally overrides the static config age with a *traced*
    value (the simulated-device-clock path, mirroring `wire_readout`'s
    r_wire override): a scalar ages the whole stack, a vector of leading-
    axis extent ages each tile of a (..., r, c) stack independently (the
    block-repair path, where repaired arrays are younger than their
    neighbours).  Ages below t0 = 1 s clamp to 1 (a freshly programmed
    device has not drifted), and `drift_nu == 0` disables drift entirely
    whatever the override says.
    """
    if drift_t is not None:
        if ni.drift_nu == 0.0:
            return g
        t = jnp.maximum(jnp.asarray(drift_t, dtype=g.dtype), 1.0)
        factor = t ** jnp.asarray(-ni.drift_nu, dtype=g.dtype)
        if factor.ndim:
            factor = factor.reshape(
                factor.shape + (1,) * (g.ndim - factor.ndim))
        return g * factor
    if ni.drift_nu == 0.0 or ni.drift_t <= 0.0 or ni.drift_t == 1.0:
        return g
    return g * (ni.drift_t ** (-ni.drift_nu))


def wire_readout(g: jnp.ndarray, ni: NonidealConfig,
                 r_wire=None) -> jnp.ndarray:
    """Dispatch the configured wire model over a (..., r, c) stack.

    `r_wire` optionally overrides `ni.r_wire` with a *traced* scalar: the
    override always routes through the differentiable first-order model,
    regardless of `ni.wire_model` / `ni.r_wire` gating (the calibration
    loops in `repro.calib` differentiate solver outputs with respect to
    it; the exact "nodal" model needs a static r_seg and stays the
    non-differentiable oracle).
    """
    if r_wire is not None:
        return _over_tiles(partial(effective_conductance, r_seg=r_wire), g)
    if ni.r_wire <= 0.0 or ni.wire_model == "none":
        return g
    if ni.wire_model == "first_order":
        return _over_tiles(partial(effective_conductance, r_seg=ni.r_wire), g)
    if ni.wire_model == "nodal":
        from repro.physics import nodal as _nodal
        return _over_tiles(
            partial(_nodal.nodal_effective_conductance, r_seg=ni.r_wire), g)
    raise ValueError(f"unknown wire_model: {ni.wire_model!r}")
