"""The comparison that decides ``correct``: one simulated call of the
program against the plain reference (``bench/reference.py``) from the same
seed.

Numbers compared (each is held to a limit of its cell's
``bench/limits/<workload>.json``; see PERF.md for the readings behind them):

* ``int_mismatch`` -- epochs of the lockstep (``reference.simulate``) in
  which the program's integer dynamics differ from the reference's: starts,
  uploads, energy spent, delivered uploads and the sum of VAoI ages, plus the
  epochs whose age sum no settling of the ambiguous decisions can give, plus,
  when the lockstep held to the end, the clients whose final age, battery or
  pending flag differ.  Exact: limit 0.
* ``probe_m_gap`` -- the largest gap of the mean VAoI distance (Eq. 5) over
  the lockstep's epochs (at least epoch 0).  Under VAoI the lockstep ends at
  the first epoch after a training, so these distances all read the initial
  model on every client's probe images against a zero moment: the probe
  forward and Eq. 5, at the timed sizes, before default-precision SGD has
  moved anything.
* ``param_change_gap`` -- by the worst leaf, the gap between the norms of the
  global model's change over the call, ``|G_final - G_init|``, of the
  program and of the reference, measured against the reference's norm of
  that leaf or of the median leaf, whichever is larger.  Norms and not the
  norm of the difference: past the lockstep the two trajectories schedule
  different clients, while a norm still reads what training and FedAvg did.
  Leaves whose reference change is under a thousandth of the median leaf's
  are left out.
* ``fedavg_gap`` -- FedAvg, teacher-forced, where the lockstep held to the
  end (as under ``fedavg``: the same clients trained and uploaded every
  epoch): the program's final global model against the plain mean of the
  program's own final messages of the reference's last uploaders, by the
  worst leaf, over that mean's change from the initial model.  Left out
  where the lockstep ended early: past it the two sides aggregated
  different clients.
* ``age_level_gap`` -- Alg. 2's age weighting, which the lockstep never
  exercises (before any training every distance lies below ``mu`` at the
  paper's settings, so every age stays 0): the gap between the program's and
  the reference's mean VAoI age over the call, over the reference's mean or
  1, whichever is larger.  Past the lockstep the two trajectories schedule
  different clients, but a top-k that favours fresh clients lets the ages
  of the others grow without bound.
* ``f1_gap`` -- the program's last macro-F1 against the reference's forward
  on the program's final global model: the eval layer.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import numpy as np

INT_METRICS = ("n_started", "n_uploaded", "energy")


def age_sums(avg_age: np.ndarray, num_clients: int) -> np.ndarray:
    """The program reports ``sum(age) / N`` in float32; ages are whole
    numbers, so the sum is recovered exactly by rounding."""
    return np.rint(np.asarray(avg_age, np.float64) * num_clients).astype(np.int64)


def numbers(prog: Dict[str, Any], ref: Dict[str, Any], f1_recomputed: float,
            num_clients: int) -> Dict[str, float]:
    """Every number the comparison reads of one call; which of them are held
    to a limit is the cell's choice (``bench/limits``)."""
    pm, rm = prog["metrics"], ref["metrics"]
    t_stop, epochs = ref["t_stop"], len(np.asarray(pm["energy"]))
    mism = int(ref["witness_faults"])
    sums = age_sums(pm["avg_age"], num_clients)
    for t in range(t_stop):
        bad = any(int(np.asarray(pm[k])[t]) != int(rm[k][t]) for k in INT_METRICS)
        bad |= int(np.asarray(pm["n_delivered"])[t]) != int(rm["n_uploaded"][t])
        bad |= int(sums[t]) != int(rm["age_sum"][t])
        mism += int(bad)
    if t_stop == epochs:
        for k in ("age", "battery", "pending"):
            mism += int(np.sum(np.asarray(prog["final"][k]).astype(np.int64)
                               != np.asarray(ref["final"][k]).astype(np.int64)))
    # epoch 0's probe reads the initial model against zero moments whatever
    # the lockstep decided, so it is always compared
    probe = max(t_stop, 1)
    gap_m = float(np.max(np.abs(np.asarray(pm["avg_m"], np.float64)[:probe]
                                - np.asarray(rm["avg_m"], np.float64)[:probe])))
    age_p = float(np.mean(np.asarray(pm["avg_age"], np.float64)))
    age_r = float(np.mean(np.asarray(rm["avg_age"], np.float64)))
    out = {
        "int_mismatch": float(mism),
        "probe_m_gap": gap_m,
        "age_level_gap": abs(age_p - age_r) / max(age_r, 1.0),
        "param_change_gap": param_change_gap(prog["global_params"], ref["global_params"],
                                             ref["init_params"]),
        "f1_gap": abs(float(np.asarray(pm["f1"])[-1]) - f1_recomputed),
        "lockstep_epochs": float(t_stop),
        "mean_age": age_p,
        "mean_age_reference": age_r,
    }
    if t_stop == epochs:
        init, msgs, ups = ref["init_params"], prog["msg_params"], ref["last_uploads"]
        out["fedavg_gap"] = worst_leaf_gap(
            prog["global_params"],
            {k: np.mean(np.asarray(msgs[k], np.float64)[ups], axis=0) for k in init},
            init) if ups.size else 0.0
    return out


def param_change_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
                     init: Dict[str, np.ndarray]) -> float:
    norm = lambda a, b: float(np.linalg.norm((np.asarray(a, np.float64) - b).ravel()))
    init64 = {k: np.asarray(v, np.float64) for k, v in init.items()}
    ref = {k: norm(want[k], init64[k]) for k in init}
    med = float(np.median(list(ref.values())))
    if med == 0.0:  # the reference's model never moved: any move is a gap
        return max(norm(got[k], init64[k]) for k in init)
    gaps = [abs(norm(got[k], init64[k]) - ref[k]) / max(ref[k], med)
            for k in init if ref[k] >= 1e-3 * med]
    return max(gaps)


def worst_leaf_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
                   init: Dict[str, np.ndarray]) -> float:
    """By the worst leaf: ``|got - want|`` over ``|want - init|`` of that
    leaf or of the median leaf, whichever is larger.  Leaves whose change is
    under a thousandth of the median leaf's are left out."""
    diff = lambda a, b: float(np.linalg.norm((np.asarray(a, np.float64) - b).ravel()))
    change = {k: diff(want[k], np.asarray(init[k], np.float64)) for k in init}
    med = float(np.median(list(change.values())))
    if med == 0.0:
        return max(diff(got[k], np.asarray(want[k], np.float64)) for k in init)
    return max(diff(got[k], np.asarray(want[k], np.float64)) / max(change[k], med)
               for k in init if change[k] >= 1e-3 * med)


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, List[List]]:
    """Each limited number beside its limit; ``correct`` when every one is
    there, finite and at or under it."""
    rows = [[k, nums.get(k, math.nan), lim] for k, lim in limits.items()]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
