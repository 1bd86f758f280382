"""GaLore-style low-rank optimizer built on the paper's randomized SVD.

For each 2-D weight (m x n, m <= n wlog) the Adam moments live in an r-dim
projected space: g_proj = P^T g with P (m x r) the top-r left singular
subspace of the gradient, recomputed every `update_every` steps with
*our* randomized SVD (core/rsvd.py — the paper's Algorithm 1).  Optimizer
memory per weight drops from 2mn to 2rn + mr.

This is the paper's "large-scale PCA inside the ML pipeline" vision made
concrete: the eigensolver sits inside the training step, so its speed (the
paper's contribution) directly bounds the projection-refresh overhead.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rsvd import RSVDConfig
from repro.optim import adamw

Params = Any

# Seeds are traced through the counter RNG, so per-refresh/per-unit seeds
# reuse one compiled program on every path.  fused_sketch vmaps too (traced
# SMEM seed) and compiles for the TPU; the default stays off because on the
# CPU backend the kernel runs in Pallas interpret mode (~18x slower than
# the XLA GEMM for zero HBM benefit), and its TPU time is not measured.
_RSVD_CFG = RSVDConfig(oversample=8, power_iters=1, qr_method="cqr2", small_svd="gram")


class GaLoreLeaf(NamedTuple):
    p: jax.Array       # projection (m x r)
    m: jax.Array       # Adam m in projected space (r x n)
    v: jax.Array       # Adam v in projected space (r x n)


class GaLoreState(NamedTuple):
    step: jax.Array
    leaves: Params      # GaLoreLeaf per projected 2-D weight, None elsewhere
    dense: adamw.AdamWState  # classic Adam for non-projected leaves


def _mT(x: jax.Array) -> jax.Array:
    return jnp.swapaxes(x, -1, -2)


def _projectable(leaf: jax.Array, rank: int) -> bool:
    # 2-D weights, or scan-stacked [units, m, n] weights (batched projection)
    return leaf.ndim in (2, 3) and min(leaf.shape[-2:]) > 2 * rank


def _masked(params: Params, rank: int, keep_projected: bool) -> Params:
    """Zero-shaped stand-ins so the dense Adam state skips projected leaves."""
    def f(p):
        if _projectable(p, rank) == keep_projected:
            return p
        return jnp.zeros((1,), p.dtype)  # placeholder leaf (negligible memory)

    return jax.tree.map(f, params)


def init_state(params: Params, rank: int, seed: int = 23) -> GaLoreState:
    def mk(p):
        if not _projectable(p, rank):
            return None
        units = p.shape[:-2]  # () for 2-D, (n_units,) for scan-stacked
        m, n = p.shape[-2:]
        if m <= n:
            proj = jnp.broadcast_to(jnp.eye(m, rank, dtype=jnp.float32), units + (m, rank))
            mom = jnp.zeros(units + (rank, n), jnp.float32)
        else:
            proj = jnp.broadcast_to(jnp.eye(n, rank, dtype=jnp.float32), units + (n, rank))
            mom = jnp.zeros(units + (m, rank), jnp.float32)
        return GaLoreLeaf(proj, mom, jnp.zeros_like(mom))

    dense = adamw.init_state(_masked(params, rank, keep_projected=False))
    return GaLoreState(
        step=jnp.zeros((), jnp.int32),
        leaves=jax.tree.map(mk, params),
        dense=dense,
    )


def _refresh_projection(g: jax.Array, rank: int) -> jax.Array:
    """Top-r singular subspace of the gradient via the paper's RSVD
    (the `repro.linalg` facade; `_RSVD_CFG` pins the numerical variant).

    Scan-stacked [units, m, n] gradients refresh every unit's projection in
    ONE vmapped solve (the StackedOp execution path) — the projection-
    refresh overhead is a single kernel launch regardless of layer count."""
    from repro import linalg

    m, n = g.shape[-2:]
    if g.ndim == 3:
        if m <= n:
            u, _, _ = linalg.svd(linalg.StackedOp(g), rank, overrides=_RSVD_CFG)
            return u                  # (units, m, r)
        _, _, vt = linalg.svd(linalg.StackedOp(g), rank, overrides=_RSVD_CFG)
        return _mT(vt)                # (units, n, r)
    gf = g.astype(jnp.float32)
    if m <= n:
        u, _, _ = linalg.svd(gf, rank, overrides=_RSVD_CFG)
        return u                      # (m, r)
    _, _, vt = linalg.svd(gf, rank, overrides=_RSVD_CFG)
    return vt.T                       # (n, r)


def apply_updates(
    params: Params,
    grads: Params,
    state: GaLoreState,
    opt_cfg: adamw.AdamWConfig,
    rank: int,
    update_every: int = 200,
) -> Tuple[Params, GaLoreState, Dict[str, jax.Array]]:
    step = state.step
    refresh = (step % update_every) == 0
    lr = adamw.schedule(opt_cfg, step)
    b1c = 1 - opt_cfg.b1 ** (step.astype(jnp.float32) + 1)
    b2c = 1 - opt_cfg.b2 ** (step.astype(jnp.float32) + 1)

    def upd(p, g, leaf):
        gf = g.astype(jnp.float32)
        m_, n_ = gf.shape[-2:]
        left = m_ <= n_
        proj = jax.lax.cond(
            refresh,
            lambda: _refresh_projection(gf, rank),
            lambda: leaf.p,
        )
        # matmul broadcasts over the optional leading units axis
        g_proj = _mT(proj) @ gf if left else gf @ proj         # (..,r,n)/(..,m,r)
        m_new = opt_cfg.b1 * leaf.m + (1 - opt_cfg.b1) * g_proj
        v_new = opt_cfg.b2 * leaf.v + (1 - opt_cfg.b2) * g_proj * g_proj
        delta_proj = (m_new / b1c) / (jnp.sqrt(v_new / b2c) + opt_cfg.eps)
        delta = proj @ delta_proj if left else delta_proj @ _mT(proj)
        delta = delta + opt_cfg.weight_decay * p.astype(jnp.float32)
        new_p = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
        return new_p, GaLoreLeaf(proj, m_new, v_new)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_leaf = treedef.flatten_up_to(state.leaves)
    out_p, out_leaf = [], []
    for p, g, leaf in zip(flat_p, flat_g, flat_leaf):
        if leaf is None:
            out_p.append(p)  # handled by the dense Adam branch below
            out_leaf.append(None)
        else:
            np_, nl = upd(p, g, leaf)
            out_p.append(np_)
            out_leaf.append(nl)
    new_params_proj = jax.tree.unflatten(treedef, out_p)
    new_leaves = jax.tree.unflatten(treedef, out_leaf)

    # dense Adam on the remaining (non-projected) leaves
    masked_params = _masked(params, rank, keep_projected=False)
    masked_grads = _masked(grads, rank, keep_projected=False)
    dense_params, dense_state, _ = adamw.apply_updates(
        masked_params, masked_grads, state.dense, opt_cfg
    )

    def merge(p, proj_p, dense_p):
        return proj_p if _projectable(p, rank) else dense_p

    new_params = jax.tree.map(merge, params, new_params_proj, dense_params)
    metrics = {"galore_refresh": refresh.astype(jnp.float32), "lr": lr}
    return new_params, GaLoreState(step + 1, new_leaves, dense_state), metrics


def memory_savings(params: Params, rank: int) -> Tuple[int, int]:
    """(dense Adam floats, GaLore floats) across projected leaves."""
    dense = 0
    lowrank = 0
    for p in jax.tree.leaves(params):
        if _projectable(p, rank):
            units = int(np.prod(p.shape[:-2])) if p.ndim > 2 else 1
            m, n = p.shape[-2:]
            dense += units * 2 * m * n
            r = rank
            lowrank += units * ((min(m, n) * r) + 2 * r * max(m, n))
    return dense, lowrank
