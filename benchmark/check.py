"""The comparison that decides ``correct``.

What the timed path produced (the model of the window's last unit, as
the traffic kind's ``answer`` hands it over) against the plain
reference's fit of the same data, number by number, each beside a limit
of its own from ``benchmark/limits/<cell>.json``, which also holds the
two chip readings the limit was set between (PERF.md section 6).

Numbers:

- ``coef.<coordinate>``: |W - W_ref|_F / |W_ref|_F of that coordinate's
  coefficient table (all entities, the planner's packing and both solver
  routes behind them).
- ``entity_max.<coordinate>`` (random effects): the widest gap of one
  entity, max_e |w_e - w_ref,e|_2 over the RMS of the reference's entity
  norms. One entity left untrained reads about 1 here and a few
  thousandths in ``coef.*``.
- ``score_rms``: RMS over ALL rows of the model's margin minus the
  reference's, over the RMS of the reference's margins: the comparison in
  prediction space.
- Where the reference states no finite answer for an entity (a logistic
  entity whose training rows carry one label has no minimiser: its
  reference row is zeros and an intercept of +-inf, and the margins of its
  rows are +-inf), that entity is in none of the numbers above and its
  rows are in no ``score_rms``. Such entities have a number of their own,
  ``unbounded.<coordinate>``: the largest exp(-s w) over the coefficients
  the reference states as s x inf, w being the model's: the odds the model
  leaves against the only label the entity trained on. A fit that pushes
  each such intercept far to its side reads e^-|w|; an entity left
  untrained reads 1. A coordinate without such entities has no such
  number, and its cell no limit for it.
- ``repeat_max_abs`` (refit): largest |difference| between the tables of
  the window's first fit and its last; the fits solve one problem.
- ``saved_max_abs`` (job): largest |difference| between the model read
  back from what the job saved and the model it held in memory. The other
  numbers of a job cell are taken on the model as read back.
"""

from __future__ import annotations

import math

import numpy as np


def _max_abs(a: dict, b: dict) -> float:
    return max(float(np.max(np.abs(a[k] - b[k]))) for k in a)


def compare(config: dict, data, answer: dict, ref_tables: dict,
            predict) -> dict:
    """``predict`` is the ``predict`` of the configuration's reference
    (``Manifest.reference``), the one that made ``ref_tables``."""
    tables = answer["tables"]
    numbers = {}
    for name, ref in ref_tables.items():
        got, ref = tables[name].astype(np.float64), ref.astype(np.float64)
        if ref.ndim == 2:
            stated = np.all(np.isfinite(ref), axis=1)
            if not stated.all():
                side = np.isinf(ref)
                numbers[f"unbounded.{name}"] = float(np.exp(np.max(
                    -np.sign(ref[side]) * got[side])))
            got, ref = got[stated], ref[stated]
        gap = got - ref
        numbers[f"coef.{name}"] = float(
            np.linalg.norm(gap) / np.linalg.norm(ref))
        if ref.ndim == 2:
            numbers[f"entity_max.{name}"] = float(
                np.sqrt(np.max(np.sum(gap ** 2, axis=1))
                        / np.mean(np.sum(ref ** 2, axis=1))))
    z = predict(config, data, tables).astype(np.float64)
    z_ref = predict(config, data, ref_tables).astype(np.float64)
    stated = np.isfinite(z_ref)
    numbers["score_rms"] = float(math.sqrt(
        np.mean((z[stated] - z_ref[stated]) ** 2)
        / np.mean(z_ref[stated] ** 2)))
    if "repeat_of" in answer:
        numbers["repeat_max_abs"] = _max_abs(tables, answer["repeat_of"])
    if "saved_from" in answer:
        numbers["saved_max_abs"] = _max_abs(tables, answer["saved_from"])
    return numbers


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit", "ok"}}). Every limit needs its
    number; a number that is missing or not finite is not correct."""
    compared, correct = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = (value is not None and math.isfinite(value)
              and value <= float(limit))
        compared[name] = {"value": value, "limit": float(limit), "ok": ok}
        correct = correct and ok
    return correct and bool(limits), compared
