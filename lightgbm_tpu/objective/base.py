"""Objective-function interface.

Reference analog: include/LightGBM/objective_function.h:19 (abstract
``ObjectiveFunction``: Init / GetGradients / BoostFromScore / ConvertOutput /
RenewTreeOutput) and the CUDA objective slice (src/objective/cuda/) whose
point is device-resident gradients — here every ``get_gradients`` is pure jnp
elementwise math, jit-fused into the boosting step, so gradients never touch
the host (the ``boosting_on_gpu_`` property of cuda_exp, gbdt.cpp:101).

Scores and gradients for multi-model objectives (multiclass) are shaped
``[K, n]`` (class-major), matching the reference's score layout.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset_core import Metadata
from ..utils import log


class ObjectiveFunction:
    """Base class. Subclasses set NAME and implement get_gradients."""

    NAME = "none"
    # True when get_gradients reads Python-side per-iteration state (e.g.
    # RankXENDCG's noise key) and therefore must not be jit-cached
    STATEFUL_GRADIENTS = False

    def __init__(self, config: Config):
        self.config = config
        self.num_data = 0
        self.label: Optional[jnp.ndarray] = None
        self.weight: Optional[jnp.ndarray] = None

    # ---- lifecycle ----------------------------------------------------
    def init(self, metadata: Metadata, num_data: int) -> None:
        self.num_data = num_data
        if metadata.label is None:
            log.fatal("Objective %s requires labels", self.NAME)
        self.check_label(metadata.label)
        self.label = jnp.asarray(metadata.label, dtype=jnp.float32)
        self.weight = (None if metadata.weight is None
                       else jnp.asarray(metadata.weight, dtype=jnp.float32))

    def check_label(self, label: np.ndarray) -> None:
        pass

    def _global_sums(self, *vals: float):
        """Sum scalars across processes when training on
        pre-partitioned multi-process data (the reference objectives'
        Network::GlobalSyncUpBy* calls, e.g. binary_objective.hpp:75);
        identity otherwise."""
        if not getattr(self.config, "pre_partition", False):
            return vals if len(vals) > 1 else vals[0]
        from ..parallel.network import Network
        if not Network.is_initialized() or Network.num_machines() <= 1:
            return vals if len(vals) > 1 else vals[0]
        out = tuple(float(v) for v in Network.global_sum(
            [float(v) for v in vals]))
        return out if len(out) > 1 else out[0]

    # ---- per-iteration ------------------------------------------------
    def get_gradients(self, score: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """score -> (grad, hess), all [n] (or [K, n])."""
        raise NotImplementedError

    def span_args(self) -> dict:
        """Host-derived counters of the gradient pass, set as args of
        the ``Boosting`` span (obs/tracer.py); none by default."""
        return {}

    def boost_from_score(self) -> np.ndarray:
        """Initial raw score(s) (reference BoostFromScore; one per model)."""
        return np.zeros(self.num_models(), dtype=np.float64)

    def convert_output(self, raw: jnp.ndarray) -> jnp.ndarray:
        """Raw score -> output space (sigmoid/exp/softmax); identity default."""
        return raw

    # ---- leaf refit (reference RenewTreeOutput, objective_function.h:46) ---
    NEEDS_RENEW = False

    def renew_leaf_percentile(self) -> Optional[float]:
        """For percentile-refit objectives: the percentile in (0,1)."""
        return None

    def leaf_residual(self, score: jnp.ndarray) -> jnp.ndarray:
        """Residual whose per-leaf percentile becomes the leaf output."""
        return self.label - score

    def renew_weight(self):
        """Percentile weights for the leaf refit: the reference uses
        sample weights when present (WeightedPercentileFun) and the
        position-interpolating PercentileFun otherwise; mape overrides
        with its label weights (regression_objective.hpp:650)."""
        return self.weight

    # ---- shape info ---------------------------------------------------
    def num_models(self) -> int:
        """Trees per boosting iteration (reference NumModelPerIteration)."""
        return 1

    def num_prediction_per_row(self) -> int:
        return self.num_models()

    @property
    def is_constant_hessian(self) -> bool:
        return False

    def _apply_weight(self, grad, hess):
        if self.weight is not None:
            grad = grad * self.weight
            hess = hess * self.weight
        return grad, hess

    def __str__(self) -> str:  # model file objective string
        return self.NAME
