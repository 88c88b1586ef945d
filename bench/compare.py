"""The numbers that decide ``correct``, computed the same way in every
cell.  Each is a gap between what the timed path produced and what the
plain reference computes from the same inputs, so that 0 is exact
agreement; the limits live in ``bench/limits/<cell>.json``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import numpy as np

#: gradient entries under this share of the median leaf's root-mean-square
#: reference gradient are nought to rounding (a key's bias under softmax):
#: Adam moves them by round-off alone, so they are left out of the norms
ZERO_GRAD_SHARE = 1e-3


def flat(tree) -> Dict[str, np.ndarray]:
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for p, v in leaves}


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-30)


def moved_entries(ref_grads) -> Tuple[Dict[str, np.ndarray], list]:
    """Per leaf, the entries whose reference gradient counts; and the
    leaves left out whole."""
    g = flat(ref_grads)
    rms = [float(np.sqrt(np.mean(np.square(v)))) for v in g.values()]
    floor = ZERO_GRAD_SHARE * float(np.median(rms))
    masks = {k: np.abs(v) >= floor for k, v in g.items()}
    return ({k: m for k, m in masks.items() if m.any()},
            sorted(k for k, m in masks.items() if not m.any()))


def leaf_gaps(got, want, masks) -> Dict[str, float]:
    """Per leaf, the gap between the program's and the reference's norm
    (over its counted entries), measured against the reference's norm of
    that leaf or of the median leaf, whichever is larger (some gradients
    are all but zero)."""
    g, w = flat(got), flat(want)
    norms = {k: float(np.linalg.norm(w[k][m])) for k, m in masks.items()}
    med = float(np.median(list(norms.values())))
    out = {}
    for k, m in masks.items():
        gap = abs(float(np.linalg.norm(g[k][m])) - norms[k]) / max(
            norms[k], med, 1e-30)
        out[k] = gap if math.isfinite(gap) else math.inf
    return out


def max_abs_gap(got, want) -> float:
    d = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    return float(np.max(d)) if d.size else 0.0


def train_numbers(prog: dict, ref: dict, params0
                  ) -> Tuple[Dict[str, float], Dict[str, str]]:
    """Numbers of a training cell's first three steps.

    prog: ``losses`` (3,), ``log_pf`` [(T, B)] per step (the rollout's own
    per-step log-probs), ``log_r`` [(B,)], ``grads`` (the first gradient as
    the optimizer received it), ``params`` (after three steps), and
    ``illegal`` (actions the replay found illegal); ref: the output of
    ``refops.train_three_steps`` on the same batches.

    ``loss`` is the first step's relative gap and ``loss3`` the widest
    of the three; ``log_pf`` the widest gap of a rollout log-prob and
    ``log_pf_mean`` the mean gap (padding steps count as exact); ``grad`` and ``update`` are the worst leaf's gap of the
    first gradient and of the parameters' change after three steps,
    ``grad_median`` and ``update_median`` the median leaf's.  A cell's
    limits file names the numbers it compares.  Also returns the worst
    leaves and what was left out, by name."""
    keep, left_out = moved_entries(ref["grads"])
    tmap = jax.tree_util.tree_map
    d_prog = tmap(lambda a, b: np.asarray(a, np.float64) - np.asarray(
        b, np.float64), prog["params"], params0)
    d_ref = tmap(lambda a, b: np.asarray(a, np.float64) - np.asarray(
        b, np.float64), ref["params"], params0)
    steps = ref["steps"]
    grads = leaf_gaps(prog["grads"], ref["grads"], keep)
    updates = leaf_gaps(d_prog, d_ref, keep)
    worst = lambda g: max(g.items(), key=lambda kv: kv[1])
    numbers = {
        "loss": rel_gap(prog["losses"][0], steps[0]["loss"]),
        "loss3": max(rel_gap(p, r["loss"]) for p, r in
                     zip(prog["losses"], steps)),
        "log_pf": max(max_abs_gap(p, r["log_pf"]) for p, r in
                      zip(prog["log_pf"], steps)),
        "log_pf_mean": float(np.mean([np.mean(np.abs(
            np.asarray(p, np.float64) - r["log_pf"])) for p, r in
            zip(prog["log_pf"], steps)])),
        "log_r": max(max_abs_gap(p, r["log_r"]) for p, r in
                     zip(prog["log_r"], steps)),
        "grad": worst(grads)[1],
        "grad_median": float(np.median(list(grads.values()))),
        "update": worst(updates)[1],
        "update_median": float(np.median(list(updates.values()))),
        "illegal": float(prog["illegal"]),
    }
    counted = sum(int(m.sum()) for m in keep.values())
    total = sum(v.size for v in flat(ref["grads"]).values())
    return numbers, {"grad_leaf": worst(grads)[0],
                     "update_leaf": worst(updates)[0],
                     "left_out": (" ".join(left_out) or "no leaf")
                     + f"; {total - counted} of {total} entries"}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and the checks to print: every number the limits name
    finite and at or under its limit."""
    checks = {k: {"value": float(numbers[k]), "limit": float(v)}
              for k, v in limits.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
