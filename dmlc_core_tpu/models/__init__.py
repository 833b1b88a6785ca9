"""Example downstream learners over staged batches.

The reference has no models (SURVEY header: "not a tensor/training
framework"); its consumers are XGBoost/MXNet-style learners fed by
RowBlockIter. These jitted learners play that downstream role for the TPU
build — small, pure-functional, and the flagship (sparse logistic
regression, the classic rabit/ps-lite workload) is what __graft_entry__
exercises; the chip benchmark trains the factorization machine.
"""

from .common import sgd_update
from .ffm import FieldAwareFM
from .fm import FactorizationMachine
from .linear import LinearRegression, LogisticRegression

__all__ = [
    "LinearRegression",
    "LogisticRegression",
    "FactorizationMachine",
    "FieldAwareFM",
    "sgd_update",
]
