"""Which part of a fit each device operation belongs to: the phase words,
and the rule that puts an operation down to one of them.

A fit's device time falls into four phases, a fifth that runs once per
prepared data set, and what the program names none for:

- ``residual``: the other coordinates' scores taken off the total and
  moved to where a coordinate trains on them (row -> slab);
- ``score``: a coordinate's new scores computed and moved back to rows,
  and the loop's update of its running total;
- ``re_solve``: a random effect's per-entity solves, every route;
- ``fe_solve``: a fixed effect's optimizer;
- ``materialize``: the fused fit's slabs built (once a data set);
- ``unphased``: what neither the scopes nor the program name place.

An operation is known by two labels the device trace keeps: its scope
path (the ``tf_op`` stat, the ``op_name`` of its HLO metadata, e.g.
``jit(_fit_fn)/while/body/coord.per-user/residual/gather``) and its
program (the HLO module, ``jit__fit_fn``; where the trace does not name
it, the head of the path). ``phase_of`` reads them:
the innermost part of the path that names a phase wins, a part being a
scope word (``SCOPES``, and ``solve.<route>``) or a nested program
``jit(<name>)`` of ``PROGRAMS``; with none, the program's own phase;
with neither, ``unphased``. A ``solve.<route>`` is a random effect's
inside ``_solve_block`` (nested, or the module the unfused loop
dispatches) and the fixed effect's anywhere else: the fused fit calls
``_run_impl`` unjitted, so its path is ``coord.<cid>/solve.lbfgs/...``.

Names only, and no scope of its own. JAX's persistent compile cache
strips debug information, ``op_name`` included, before it hashes a
program, so a scope added to a program whose operations do not change
is not in the executable a warm cache serves, and a trace read from it
does not show it. A program's NAME is in the key: a phase that no scope
the programs have long written gives comes from ``PROGRAMS``.
"""

from __future__ import annotations

PHASES = ("residual", "score", "re_solve", "fe_solve", "materialize")
UNPHASED = "unphased"

# The scope words the programs write (jax.named_scope): fused_fit.py's
# coord.<cid>/residual, /score, /materialize, /solve.<optimizer>;
# random_effect.py's residual and solve.<route>.
SCOPES = {
    "residual": "residual",
    "score": "score",
    "materialize": "materialize",
}
SOLVE_SCOPE = "solve."
RE_SOLVE_PROGRAM = "_solve_block"

# The repository's own jitted programs, by the name of the function
# jitted: a module ``jit_<name>``, or ``jit(<name>)`` inside another
# program's path. None: a program with no phase of its own (its scopes
# say it, or it is not a phase of a fit).
PROGRAMS = {
    # the fused fit
    "_fit_fn": None,
    "_mat_fn": "materialize",
    # the solves, dispatched alone by the unfused loop, inlined in _fit_fn
    "_run_impl": "fe_solve",
    "_solve_block": "re_solve",
    # the random-effect scorers (models/game.py)
    "_gather_score": "score",
    "_gather_score_mesh": "score",
    "_bucket_score_add": "score",
    "_passive_score_set_dense": "score",
    "_passive_score_set_sparse": "score",
    "_score_raw_dense": "score",
    "_score_raw_sparse": "score",
    # the unfused loop's own (algorithm/coordinate_descent.py)
    "_sub_add_impl": "score",
    "_placed_residuals": "residual",
    "_all_finite": None,  # the non-finite guard, off in every cell
}

# JAX's one-primitive programs that the unfused loop and its fixed
# effect dispatch eagerly, by module name only: ``jit(matmul)`` inside a
# program's path is that program's own product.
EAGER = {
    "matmul": "score",  # FixedEffectCoordinate.score: features x means
    # the loop's total - old, and the fixed effect's offsets + residuals
    # (the loop's first total + scores of a coordinate rides along,
    # twice a fit)
    "subtract": "residual",
    "add": "residual",
    # scalar operands cast and zero tables of a train call: no phase
    "convert_element_type": None,
    "broadcast_in_dim": None,
}


def program_name(module: str) -> str:
    """``jit__fit_fn`` -> ``_fit_fn``; a name without the prefix stays."""
    return module[4:] if module.startswith("jit_") else module


def _nested(part: str) -> str:
    """``jit(<name>)`` -> ``<name>``; any other part -> ``""``."""
    return part[4:-1] if part.startswith("jit(") and part.endswith(")") \
        else ""


def _parts(tf_op: str) -> list:
    """A scope path's parts; a TPU trace ends ``tf_op`` in ``:<type>``."""
    return tf_op.rsplit(":", 1)[0].split("/") if tf_op else []


def program_of(tf_op: str) -> str:
    """The program at the head of a scope path (``jit(_fit_fn)/...`` ->
    ``_fit_fn``), or ``""``."""
    parts = _parts(tf_op)
    return _nested(parts[0]) if parts else ""


def phase_of(tf_op: str, program: str) -> str:
    """The phase of one operation: ``tf_op`` its scope path (may be
    empty), ``program`` its module's or function's name (may be empty)."""
    parts = _parts(tf_op)
    name = program_name(program or "") or program_of(tf_op)
    for part in reversed(parts):
        if part.startswith(SOLVE_SCOPE):
            inside = {name} | {_nested(p) for p in parts}
            return "re_solve" if RE_SOLVE_PROGRAM in inside else "fe_solve"
        phase = SCOPES.get(part) or PROGRAMS.get(_nested(part))
        if phase is not None:
            return phase
    phase = PROGRAMS.get(name, EAGER.get(name))
    return UNPHASED if phase is None else phase
