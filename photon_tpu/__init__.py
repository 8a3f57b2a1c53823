"""photon-tpu: a TPU-native GLM / GLMix (GAME) training framework.

A from-scratch JAX/XLA re-design of the capabilities of LinkedIn Photon-ML
(Spark/Scala): generalized linear models (linear, logistic, Poisson,
smoothed-hinge SVM), GLMix mixed-effect models trained by block coordinate
descent, L-BFGS / OWL-QN / TRON optimizers, normalization, evaluation,
hyperparameter tuning, and Avro-compatible model I/O — with Spark RDD
machinery replaced by sharded device arrays, XLA collectives, and vmapped
batched per-entity solvers.
"""

import os as _os

__version__ = "0.1.0"

# The checkout: the directory that holds this package. What the program
# builds at run time (the compile cache, the native Avro decoder) lives
# beside the package, git-ignored — never under ~ and never at a
# temporary, pid- or time-derived name.
CHECKOUT_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
