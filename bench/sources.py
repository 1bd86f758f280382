"""What a configuration says about the program's inputs, beside the
matrices themselves: the run seed's words, the spec each call passes, and
the placement of the pool over the cell's chips.

The matrices are a kind's (bench/kinds/<kind>.py, found by the
configuration's "kind", "dense_spectrum" where it gives none).  A kind
module has three functions:

- build_pool(cfg, traffic, seed, devices): the pool, on the cell's
  devices; `pool.members[j]` is the matrix that call j factors, and
  `pool.sharding`, where the pool has one, the (mesh, axis) its members are
  row-sharded on; the rest is whatever the kind's judge needs;
- judge(pool, member, factors, cfg): the numbers of one solve, which the
  cell's limits file names;
- required_work(cfg): {"flops", "bytes"} of one solve, from the
  configuration's sizes alone.

The configuration's optional fields, each absent meaning what it always
did:

- "kind": the module that builds, judges and prices the pool;
- "spec": absent, `Rank(rank)`; {"kind": "tolerance", "eps": e},
  `Tolerance(eps=e)`, the program's Frobenius relative-error target;
- "placement": absent, every member on the cell's one chip; "rows", every
  member row-sharded over the cell's chips on a 1-D mesh, passed to the
  program as `ShardedOp(A, mesh, "rows")`.

Everything here is the benchmark's own: it imports nothing of the program,
and is handed `repro.linalg` where it builds the program's arguments.
"""
from __future__ import annotations

import numbers

import jax
import numpy as np

PLACEMENTS = ("rows",)
#: the mesh axis of a row-sharded pool
ROWS_AXIS = "rows"


def seed_key(seed):
    """A PRNG key from a uint32[2] array holding the run seed's low and high
    words, so every seed runs the same compiled program."""
    return jax.random.fold_in(jax.random.key(seed[0]), seed[1])


def seed_words(seed: int) -> np.ndarray:
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2^64)")
    return np.array([seed % 2 ** 32, seed // 2 ** 32], np.uint32)


def spec(cfg: dict) -> dict:
    """The configuration's spec as plain data: {"kind": "rank", "k": rank}
    where it states none, or {"kind": "tolerance", "eps": eps}."""
    s = cfg.get("spec")
    if s is None:
        return {"kind": "rank", "k": cfg["rank"]}
    eps = s.get("eps") if isinstance(s, dict) else None
    if (not isinstance(s, dict) or s.get("kind") != "tolerance"
            or isinstance(eps, bool) or not isinstance(eps, numbers.Real)
            or not 0 < eps < 1):
        raise ValueError(f"configuration {cfg['name']}: spec must be absent or "
                         '{"kind": "tolerance", "eps": e} with 0 < e < 1')
    return {"kind": "tolerance", "eps": float(eps)}


def program_spec(linalg, cfg: dict):
    """The spec object every call of the cell passes to the program."""
    s = spec(cfg)
    if s["kind"] == "rank":
        return linalg.Rank(s["k"])
    return linalg.Tolerance(eps=s["eps"])


def placement(cfg: dict, traffic: dict, chips: int):
    """The configuration's placement over the cell's chips, checked before
    set-up: a cell on more than one chip has to say how its matrices lie
    over them, and is never run on one chip alone."""
    where = cfg.get("placement")
    if where is not None and where not in PLACEMENTS:
        raise ValueError(f"configuration {cfg['name']}: placement must be absent "
                         f"or one of {PLACEMENTS}, not {where!r}")
    if chips > 1 and where is None:
        raise ValueError(f"configuration {cfg['name']} names no placement, and its "
                         f"cell asks for {chips} chips: it would run on one")
    if where == "rows" and traffic["residency"] != "device":
        raise ValueError(f"configuration {cfg['name']}: a row-sharded pool lives "
                         f"on the device, not in {traffic['residency']!r} memory")
    return where


def row_mesh(devices):
    """The 1-D mesh a row-sharded pool lies on."""
    return jax.sharding.Mesh(np.array(devices), (ROWS_AXIS,))


def program_inputs(linalg, pool) -> list:
    """What each call passes to the program for each pool member: the
    member as it is, or, where the pool has a `sharding`, the member
    wrapped in `ShardedOp` as a user of a sharded matrix does."""
    if getattr(pool, "sharding", None) is None:
        return list(pool.members)
    mesh, axis = pool.sharding
    return [linalg.ShardedOp(A, mesh, axis) for A in pool.members]
