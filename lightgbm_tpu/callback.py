"""Training callbacks.

Reference: python-package/lightgbm/callback.py — the same callback protocol:
callables taking a CallbackEnv namedtuple, ``before_iteration`` attribute for
pre-iteration callbacks, EarlyStopException control flow.
"""
from __future__ import annotations

import collections
from typing import Any, Callable, Dict, List, Optional, Union

# bound at import time so a module purge/reimport (tests/test_fused.py,
# tools/tpu_smoke.py) keeps each library generation's callback, booster
# and counter store consistent with ONE tracer instance
from .obs import counters as obs_counters
from .obs import ledger as obs_ledger
from .obs import tracer as obs_tracer
from .utils import log

__all__ = ["early_stopping", "log_evaluation", "record_evaluation",
           "reset_parameter", "CallbackEnv", "EarlyStopException",
           "TraceCallback"]

CallbackEnv = collections.namedtuple(
    "CallbackEnv",
    ["model", "params", "iteration", "begin_iteration", "end_iteration",
     "evaluation_result_list"])


class EarlyStopException(Exception):
    def __init__(self, best_iteration: int, best_score):
        super().__init__()
        self.best_iteration = best_iteration
        self.best_score = best_score


def log_evaluation(period: int = 1, show_stdv: bool = True) -> Callable:
    def _callback(env: CallbackEnv) -> None:
        if period > 0 and env.evaluation_result_list \
                and (env.iteration + 1) % period == 0:
            result = "\t".join(
                _format_eval_result(x, show_stdv)
                for x in env.evaluation_result_list)
            log.info("[%d]\t%s", env.iteration + 1, result)
    _callback.order = 10
    return _callback


def _format_eval_result(value, show_stdv: bool = True) -> str:
    if len(value) == 4:
        return f"{value[0]}'s {value[1]}: {value[2]:g}"
    if len(value) == 5:  # cv: with stdv
        if show_stdv:
            return f"{value[0]}'s {value[1]}: {value[2]:g} + {value[4]:g}"
        return f"{value[0]}'s {value[1]}: {value[2]:g}"
    raise ValueError("Wrong metric value")


def record_evaluation(eval_result: Dict[str, Dict[str, List[float]]]) -> Callable:
    if not isinstance(eval_result, dict):
        raise TypeError("eval_result should be a dictionary")

    def _init(env: CallbackEnv) -> None:
        eval_result.clear()
        for item in env.evaluation_result_list:
            data_name, eval_name = item[0], item[1]
            eval_result.setdefault(data_name, collections.OrderedDict())
            if len(item) == 4:
                eval_result[data_name].setdefault(eval_name, [])
            else:
                eval_result[data_name].setdefault(f"{eval_name}-mean", [])
                eval_result[data_name].setdefault(f"{eval_name}-stdv", [])

    def _callback(env: CallbackEnv) -> None:
        if not eval_result:
            _init(env)
        for item in env.evaluation_result_list:
            data_name, eval_name = item[0], item[1]
            if len(item) == 4:
                eval_result[data_name][eval_name].append(item[2])
            else:
                eval_result[data_name][f"{eval_name}-mean"].append(item[2])
                eval_result[data_name][f"{eval_name}-stdv"].append(item[4])
    _callback.order = 20
    return _callback


def reset_parameter(**kwargs) -> Callable:
    """Reset parameters on schedule (learning_rate=list or callable)."""

    def _callback(env: CallbackEnv) -> None:
        new_parameters = {}
        for key, value in kwargs.items():
            if isinstance(value, list):
                if len(value) != env.end_iteration - env.begin_iteration:
                    raise ValueError(
                        f"Length of list {key!r} has to equal to 'num_boost_round'.")
                new_param = value[env.iteration - env.begin_iteration]
            else:
                new_param = value(env.iteration - env.begin_iteration)
            new_parameters[key] = new_param
        if new_parameters:
            if "learning_rate" in new_parameters and env.model._inner is not None:
                env.model._inner.shrinkage_rate = new_parameters["learning_rate"]
                env.model._inner.config.learning_rate = new_parameters["learning_rate"]
            env.params.update(new_parameters)
    _callback.before_iteration = True
    _callback.order = 10
    return _callback


class TraceCallback:
    """Per-iteration training telemetry (the user-facing face of the
    ``lightgbm_tpu.obs`` tracer).

    Records, for every iteration: wall time since the previous
    iteration, the work counter totals (splits, rows partitioned /
    histogrammed, fused-kernel engagements — derived from each finished
    tree while tracing is on, see obs/counters.py), and the evaluation
    results.  The records
    accumulate on ``self.history`` and are mirrored into the tracer as
    instant events, so they land in the ``LGBM_TPU_TRACE`` file next to
    the phase spans.  With ``enable_trace=True`` the callback turns the
    tracer on at its first call (in-memory unless ``trace_path`` is
    given), so users get counters — from the second iteration's tree
    on — without touching env vars::

        cb = lgb.TraceCallback(period=10)
        lgb.train(params, ds, callbacks=[cb])
        print(cb.history[-1])
    """

    order = 25
    before_iteration = False

    def __init__(self, period: int = 1, logger: bool = True,
                 enable_trace: bool = True, trace_path: str = ""):
        self.period = max(int(period), 1)
        self.logger = logger
        self.enable_trace = enable_trace
        self.trace_path = trace_path
        self.history: List[Dict[str, Any]] = []
        self._last_t: Optional[float] = None
        self._i_enabled = False

    def __call__(self, env: CallbackEnv) -> None:
        import time

        if self.enable_trace and not obs_tracer.enabled:
            obs_tracer.enable(self.trace_path or None)
            self._i_enabled = True
        now = time.perf_counter()
        rec: Dict[str, Any] = {
            "iteration": env.iteration,
            "iter_wall_s": (None if self._last_t is None
                            else now - self._last_t),
            "counters": obs_counters.totals(),
            "trees": (env.model.num_trees()
                      if hasattr(env.model, "num_trees") else None),
            "eval": list(env.evaluation_result_list or []),
        }
        self._last_t = now
        self.history.append(rec)
        # the run ledger (obs/metrics.py) keeps the per-iteration
        # TRAJECTORY — phase-wall / counter / event deltas + the HBM
        # watermark — that bench/v3 records embed and `obs diff`
        # compares median-of-k; this callback is its sampling site on
        # the lgb.train path.  Gated on the tracer so an untraced run
        # (enable_trace=False) accumulates no dead all-empty rows
        if obs_tracer.enabled:
            obs_ledger.sample(env.iteration, wall_s=rec["iter_wall_s"],
                              eval_results=rec["eval"],
                              trees=rec["trees"])
        obs_tracer.instant("TraceCallback", **{
            "iteration": env.iteration, "counters": rec["counters"],
            "iter_wall_s": rec["iter_wall_s"]})
        if self.logger and (env.iteration + 1) % self.period == 0:
            c = rec["counters"]
            log.info(
                "[trace] iter %d: %.1f ms, %d splits, %d rows "
                "partitioned%s",
                env.iteration + 1,
                (rec["iter_wall_s"] or 0.0) * 1e3,
                int(c.get("splits", 0)),
                int(c.get("rows_partitioned", 0)),
                " (counters need LGBM_TPU_TRACE at Booster construction)"
                if c.get("splits", 0) == 0 else "")
        if self._i_enabled and env.iteration >= env.end_iteration - 1:
            # don't leave the process-global tracer (and its per-span
            # barriers) on after the run this callback was attached to;
            # an early-stopped run skips this — call obs.tracer.disable()
            # yourself if you stop training by exception
            obs_tracer.disable()
            self._i_enabled = False


def early_stopping(stopping_rounds: int, first_metric_only: bool = False,
                   verbose: bool = True, min_delta: Union[float, List[float]] = 0.0
                   ) -> Callable:
    """Reference callback.py:367 semantics: track every (dataset, metric)
    pair, stop when none improves for ``stopping_rounds`` iterations."""
    best_score: List[float] = []
    best_iter: List[int] = []
    best_score_list: List = []
    cmp_op: List[Callable] = []
    enabled = [True]
    first_metric = [""]

    def _init(env: CallbackEnv) -> None:
        from .config import Config
        booster_type = "gbdt"
        for key, v in (env.params or {}).items():
            if Config.canonical_name(key) == "boosting":
                booster_type = str(v)
        if booster_type == "dart":
            # dart rescales earlier trees after the fact, so a truncated
            # prefix does not reproduce the best-iteration score
            enabled[0] = False
            log.warning("Early stopping is not available in dart mode")
            return
        enabled[0] = bool(env.evaluation_result_list)
        if not enabled[0]:
            log.warning("For early stopping, at least one dataset and "
                        "eval metric is required for evaluation")
            return
        if verbose:
            log.info("Training until validation scores don't improve for %d rounds",
                     stopping_rounds)
        n_metrics = len({m[1] for m in env.evaluation_result_list})
        n_datasets = len({m[0] for m in env.evaluation_result_list})
        deltas = (min_delta if isinstance(min_delta, list)
                  else [min_delta] * n_datasets * n_metrics)
        first_metric[0] = env.evaluation_result_list[0][1].split(" ")[-1]
        for eval_ret, delta in zip(env.evaluation_result_list, deltas):
            best_iter.append(0)
            best_score_list.append(None)
            if eval_ret[3]:  # higher better
                best_score.append(float("-inf"))
                cmp_op.append(lambda x, y, d=delta: x > y + d)
            else:
                best_score.append(float("inf"))
                cmp_op.append(lambda x, y, d=delta: x < y - d)

    def _callback(env: CallbackEnv) -> None:
        if not best_score:
            _init(env)
        if not enabled[0]:
            return
        for i in range(len(env.evaluation_result_list)):
            score = env.evaluation_result_list[i][2]
            if best_score_list[i] is None or cmp_op[i](score, best_score[i]):
                best_score[i] = score
                best_iter[i] = env.iteration
                best_score_list[i] = env.evaluation_result_list
            eval_name_splitted = env.evaluation_result_list[i][1].split(" ")
            if first_metric_only and first_metric[0] != eval_name_splitted[-1]:
                continue
            if (env.evaluation_result_list[i][0] == "training"
                    and len({m[0] for m in env.evaluation_result_list}) > 1):
                continue  # train metric never triggers stopping
            if env.iteration - best_iter[i] >= stopping_rounds:
                if verbose:
                    log.info("Early stopping, best iteration is:\n[%d]\t%s",
                             best_iter[i] + 1, "\t".join(
                                 _format_eval_result(x)
                                 for x in best_score_list[i]))
                raise EarlyStopException(best_iter[i], best_score_list[i])
            if env.iteration == env.end_iteration - 1:
                if verbose:
                    log.info("Did not meet early stopping. Best iteration is:"
                             "\n[%d]\t%s", best_iter[i] + 1, "\t".join(
                                 _format_eval_result(x)
                                 for x in best_score_list[i]))
                raise EarlyStopException(best_iter[i], best_score_list[i])
    _callback.order = 30
    return _callback
