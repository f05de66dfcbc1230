"""Training API: train() and cv().

Reference: python-package/lightgbm/engine.py:28 (train) and :404 (cv) — the
same loop shape: per-iteration before/after callbacks, booster.update(),
eval collection, EarlyStopException handling, best_iteration bookkeeping.
"""
from __future__ import annotations

import copy
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .config import Config
# reset_run bound at import time (callback.py convention): after a
# module purge/reimport each generation's train() must reset ITS OWN
# counter/event/ledger stores, not the newest generation's
from .obs import ledger as obs_ledger
from .obs import pulse as pulse_mod
from .obs import reset_run as obs_reset_run
from .obs import tracer as obs_tracer
# same convention for the fault-tolerance layer (ISSUE 13): per-run
# fault reports and checkpoint policy resolve in THIS generation
from .resilience import checkpoint as ckpt_mod
from .resilience import faults as faults_mod
from .utils import log

__all__ = ["train", "cv"]


def train(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    valid_sets: Optional[Union[Dataset, Sequence[Dataset]]] = None,
    valid_names: Optional[Sequence[str]] = None,
    feval=None,
    init_model: Optional[Union[str, Booster]] = None,
    keep_training_booster: bool = False,
    callbacks: Optional[Sequence[Callable]] = None,
) -> Booster:
    # fresh per-run observability state (ISSUE 5 lifecycle): counter
    # history, event totals, the run ledger and every warn-once cache
    # restart HERE — before Booster construction, so fallbacks fired
    # while building THIS run's grower (psum warnings) are
    # attributed to this run, and nothing leaks in from a previous
    # train() in the same process.  The stores are process-global:
    # concurrent train() calls in different threads share them, so
    # per-run attribution assumes sequential runs (obs/counters.py)
    obs_reset_run()
    params = dict(params or {})
    cfg = Config.from_params(params)
    if "num_iterations" in {Config.canonical_name(k) for k in params}:
        num_boost_round = cfg.num_iterations

    fobj = None
    if callable(params.get("objective")):
        fobj = params["objective"]
        params["objective"] = "none"

    predictor = None
    if init_model is not None:
        # continued training: initialize scores with the old model's raw
        # preds AND keep its trees (reference keeps models_ and boosts on)
        predictor = (init_model if isinstance(init_model, Booster)
                     else Booster(model_file=init_model))
        if any(getattr(t, "is_linear", False) for t in predictor._models):
            # inherit linear_tree so the dataset retains raw values for
            # leaf-model replay (reference reads it from the model file)
            params.setdefault("linear_tree", True)
            train_set._update_params({"linear_tree": True})
        if train_set.init_score is None and train_set.data is not None:
            raw = predictor.predict(train_set.data, raw_score=True)
            train_set.set_init_score(np.asarray(raw, np.float64).T.reshape(-1)
                                     if raw.ndim == 2 else raw)

    booster = Booster(params=params, train_set=train_set)
    if predictor is not None:
        import copy as _copy
        booster._inner.set_init_model(
            [_copy.deepcopy(t) for t in predictor._models])
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        for i, vs in enumerate(valid_sets):
            if vs is train_set:
                # reference: training data as valid set -> name "training"
                booster._inner._train_metrics = booster._inner._train_metrics or []
                from .metric import create_metrics
                ms = create_metrics(booster.config)
                for m in ms:
                    m.init(train_set._binned.metadata, train_set._binned.num_data)
                booster._inner._train_metrics = ms
                continue
            name = (valid_names[i] if valid_names and i < len(valid_names)
                    else f"valid_{i}")
            # Booster.add_valid aligns un-constructed valid sets to the
            # training bin mappers (independently-binned matrices replay
            # garbage through bin-space trees)
            booster.add_valid(vs, name)

    cbs = list(callbacks or [])
    if cfg.early_stopping_round and cfg.early_stopping_round > 0:
        cbs.append(callback_mod.early_stopping(
            cfg.early_stopping_round, cfg.first_metric_only))
    if cfg.verbosity >= 1 and cfg.metric_freq > 0 and not any(
            getattr(c, "order", None) == 10 and not getattr(c, "before_iteration", False)
            for c in cbs):
        cbs.append(callback_mod.log_evaluation(cfg.metric_freq))
    cbs_before = [c for c in cbs if getattr(c, "before_iteration", False)]
    cbs_after = [c for c in cbs if not getattr(c, "before_iteration", False)]
    cbs_before.sort(key=lambda c: getattr(c, "order", 0))
    cbs_after.sort(key=lambda c: getattr(c, "order", 0))

    # --- fault tolerance (ISSUE 13, lightgbm_tpu/resilience) ---
    # checkpoint/resume: with LGBM_TPU_CKPT_DIR set, training resumes
    # from the latest valid ckpt/v1 snapshot (byte-identical trees vs
    # the uninterrupted run) and snapshots every LGBM_TPU_CKPT_EVERY
    # iterations.  A checkpoint from a different config fingerprint or
    # routing digest REFUSES (ResumeRefused, exit 2 at CLI layers).
    faults_mod.reset_run()
    ckpt_policy = ckpt_mod.policy_from_env()
    ckpt_dir: Optional[str] = None
    ckpt_fp: Optional[str] = None
    resumed = 0
    if ckpt_policy.dir is not None:
        unsupported = ckpt_mod.supports(booster._inner)
        if unsupported is not None:
            log.warning("checkpointing disabled for this run: %s",
                        unsupported)
        else:
            ckpt_dir = ckpt_policy.dir
            # fingerprint the config NOW, before any callback mutates
            # it (reset_parameter rewrites learning_rate in place each
            # iteration — a fingerprint of the mutated config would
            # refuse every legitimate resume)
            ckpt_fp = ckpt_mod.config_fingerprint(booster.config)
            os.makedirs(ckpt_dir, exist_ok=True)
            resumed = ckpt_mod.maybe_resume(booster, ckpt_dir,
                                            fingerprint=ckpt_fp,
                                            every=ckpt_policy.every)
            if resumed and cfg.early_stopping_round:
                # ckpt/v1 captures the boosting state, NOT callback
                # state: the pre-kill best metric is forgotten, so
                # stopping decisions restart from the resume point and
                # the final best_iteration may differ from the
                # uninterrupted run — loud, not silent
                log.warning(
                    "resumed with early_stopping_round=%d: callback "
                    "state is not part of the ckpt/v1 snapshot, so "
                    "early-stopping restarts its best-metric search "
                    "at iteration %d", cfg.early_stopping_round,
                    resumed)
    booster.resumed_from = resumed

    # live pulse heartbeats (ISSUE 20): one rate-limited beat per
    # completed iteration, strictly outside the jitted update — with
    # LGBM_TPU_PULSE=off no emitter is allocated and this whole layer
    # is a single `is None` branch per iteration (grow-pulse-off pin)
    pulse_em = pulse_mod.emitter("trainer")
    ckpt_last = resumed if ckpt_dir is not None else 0

    retries = faults_mod.max_retries()
    attempt = 0
    evaluation_result_list: List = []
    it = resumed
    if resumed >= num_boost_round:
        # the snapshot outruns this invocation's request (e.g. a
        # 100-round run died at 90, rerun with num_boost_round=50):
        # no iteration executes and the checkpointed model comes back
        # as-is — loud, because the caller asked for fewer trees than
        # they are getting
        log.warning(
            "checkpoint already holds %d iteration(s) >= "
            "num_boost_round=%d: no further training, returning the "
            "checkpointed model unchanged", resumed, num_boost_round)
    while it < num_boost_round:
        if ckpt_dir is not None:
            # a no-snapshot in-place retry (below) must rewind the
            # stateful host RNG streams the dead attempt consumed —
            # otherwise the retried tree draws a shifted feature mask
            # and the "recovered" run silently diverges from the
            # uninterrupted one (.state is a fresh dict of ints each
            # access, so holding it is a cheap snapshot)
            _inner = booster._inner
            rng_snap = (_inner._rng_feature.bit_generator.state,
                        _inner._rng_bagging.bit_generator.state)
        try:
            # the iteration span nests the booster's TrainOneIter /
            # BeforeTrain / grow-phase spans plus eval (no-op unless the
            # obs tracer is live; see lightgbm_tpu/obs)
            with obs_tracer.span("Train::iteration", iteration=it):
                for cb in cbs_before:
                    cb(callback_mod.CallbackEnv(booster, params, it, 0,
                                                num_boost_round, None))
                finished = booster.update(fobj=fobj)

                evaluation_result_list = []
                if ((it + 1) % max(cfg.metric_freq, 1) == 0
                        or cfg.early_stopping_round):
                    evaluation_result_list = (booster.eval_train(feval)
                                              + booster.eval_valid(feval))
                try:
                    # the callbacks' host work (and whatever pulls or
                    # profiler calls they make) under a name of its own
                    with obs_tracer.span("Callbacks"):
                        for cb in cbs_after:
                            cb(callback_mod.CallbackEnv(
                                booster, params, it, 0, num_boost_round,
                                evaluation_result_list))
                except callback_mod.EarlyStopException as e:
                    booster.best_iteration = e.best_iteration + 1
                    _record_best(booster, e.best_score)
                    break
                if (ckpt_dir is not None and ckpt_policy.every > 0
                        and (it + 1) % ckpt_policy.every == 0):
                    ckpt_mod.save_booster(booster, ckpt_dir,
                                          keep=ckpt_policy.keep,
                                          every=ckpt_policy.every,
                                          fingerprint=ckpt_fp)
                    ckpt_last = it + 1
                    if pulse_em is not None:
                        pulse_em.event("ckpt_save", iteration=it + 1)
                if finished:
                    break
        except (ckpt_mod.CheckpointError, ckpt_mod.ResumeRefused,
                faults_mod.FaultError):
            # these carry their own structured-finding exit contracts;
            # classifying them again would wrap the wrapper
            raise
        except Exception as e:   # noqa: BLE001 - classified below
            # engine-boundary fault policy: a KNOWN fault class is
            # classified into a faultreport/v1 finding, then either
            # recovered (resume from the last checkpoint with bounded
            # backoff) or degraded loudly as FaultError — never a raw
            # traceback.  Anything the ordered class table does not
            # recognize is a plain bug (user callback/feval/fobj,
            # programming error) and propagates untouched: wrapping it
            # would mislabel it a device fault and hide it from the
            # caller's own except clauses.
            if faults_mod.classify(e) is None:
                raise
            attempt += 1
            has_ckpt = (ckpt_dir is not None
                        and ckpt_mod.latest(ckpt_dir) is not None)
            # retry-in-place is only safe at a clean iteration
            # boundary: a multiclass iteration that died after some
            # class trees were appended + scored (e.g. a numerics
            # sentinel on class 1) would duplicate them on re-run.
            # It additionally requires that the dead attempt could not
            # have mutated state the RNG rewind below cannot restore:
            # CEGB's paid-feature mask and the carried physical comb
            # permutation both advance inside update() before a
            # sentinel can raise, and retrying on either would
            # silently fork the run — with no snapshot to roll back
            # to, those configs degrade loudly instead
            inner = booster._inner
            boundary = (len(inner.models)
                        == inner.current_iteration()
                        * inner.num_tree_per_iteration)
            inplace_ok = (
                boundary
                and getattr(inner, "_cegb_paid", None) is None
                and getattr(getattr(inner, "grow", None),
                            "reset_stream", None) is None)
            faults_mod.handle_training_fault(
                e, iteration=it, ckpt_dir=ckpt_dir, attempt=attempt,
                retries=retries, state_ok=has_ckpt or inplace_ok)
            if has_ckpt:
                it = ckpt_mod.maybe_resume(booster, ckpt_dir,
                                           fingerprint=ckpt_fp,
                                           every=ckpt_policy.every)
            else:
                # no snapshot landed yet, but the booster is at a
                # clean iteration boundary (state verified above), so
                # it still holds consistent state.  Rewind the host
                # RNG streams ONLY when the dead attempt consumed
                # draws without landing its tree — when the fault
                # fired AFTER update() completed (eval, callbacks),
                # the kept tree owns those draws and rewinding would
                # make the next tree re-draw the same feature mask,
                # silently diverging from the uninterrupted run
                if inner.current_iteration() == it:
                    inner._rng_feature.bit_generator.state = rng_snap[0]
                    inner._rng_bagging.bit_generator.state = rng_snap[1]
                it = inner.current_iteration()
                if (it > 0 and ckpt_policy.every > 0
                        and it % ckpt_policy.every == 0):
                    # the fault killed the iteration's tail after its
                    # tree landed: run the boundary save the tail
                    # skipped — each save re-anchors the physical row
                    # permutation, so dropping one would fork the
                    # save-cadence trajectory an uninterrupted run
                    # follows (the iteration's eval/early-stopping
                    # bookkeeping stays skipped; the fault report
                    # above is the loud record of that)
                    ckpt_mod.save_booster(booster, ckpt_dir,
                                          keep=ckpt_policy.keep,
                                          every=ckpt_policy.every,
                                          fingerprint=ckpt_fp)
                    ckpt_last = it
            continue
        if pulse_em is not None:
            detail: Dict[str, Any] = {}
            rows = obs_ledger.iterations if obs_tracer.enabled else []
            if rows:
                last_row = rows[-1]
                detail["ledger"] = {
                    "hbm_phase_bytes": int(sum(
                        (last_row.get("hbm_phase_bytes")
                         or {}).values())),
                    "fallback_events": int(sum(
                        n for name, n in (last_row.get("events")
                                          or {}).items()
                        if "fallback" in name)),
                }
            if ckpt_dir is not None and ckpt_policy.every > 0:
                detail["ckpt"] = {"every": ckpt_policy.every,
                                  "last": ckpt_last}
            pulse_em.beat("Train::iteration", iteration=it,
                          total=num_boost_round, **detail)
        it += 1
        # a completed iteration closes the fault incident: the retry
        # budget bounds CONSECUTIVE recovery attempts, not the total
        # transient faults a long run may survive
        attempt = 0
    if pulse_em is not None:
        # the terminal heartbeat marks a CLEAN exit: a faulted run
        # propagates above WITHOUT it, so its stream goes quiet and
        # the watchdog classifies the silent tail as STALLED
        # (faults.STALL_CLASS) instead of reading it as finished
        pulse_em.event("end", iteration=it)
    if booster.best_iteration <= 0:
        booster.best_iteration = booster.current_iteration()
        _record_best(booster, evaluation_result_list)
    return booster


def _record_best(booster: Booster, results) -> None:
    booster.best_score = {}
    for item in results or []:
        ds, metric, value = item[0], item[1], item[2]
        booster.best_score.setdefault(ds, {})[metric] = value


class CVBooster:
    """Container of per-fold boosters (reference engine.py CVBooster)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, b: Booster) -> None:
        self.boosters.append(b)

    def __getattr__(self, name):
        def handler(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler


def _make_n_folds(full_data: Dataset, nfold: int, params, seed: int,
                  stratified: bool, shuffle: bool):
    full_data.construct()
    num_data = full_data.num_data()
    rng = np.random.default_rng(seed)
    if stratified:
        label = np.asarray(full_data.get_label())
        folds_idx = [[] for _ in range(nfold)]
        for c in np.unique(label):
            idx_c = np.flatnonzero(label == c)
            if shuffle:
                rng.shuffle(idx_c)
            for i, part in enumerate(np.array_split(idx_c, nfold)):
                folds_idx[i].append(part)
        folds_idx = [np.concatenate(parts) for parts in folds_idx]
    else:
        idx = np.arange(num_data)
        if shuffle:
            rng.shuffle(idx)
        folds_idx = np.array_split(idx, nfold)
    for i in range(nfold):
        test_idx = np.sort(np.asarray(folds_idx[i]))
        train_idx = np.sort(np.concatenate(
            [folds_idx[j] for j in range(nfold) if j != i]))
        yield train_idx, test_idx


def cv(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    folds=None,
    nfold: int = 5,
    stratified: bool = True,
    shuffle: bool = True,
    metrics=None,
    feval=None,
    init_model=None,
    seed: int = 0,
    callbacks: Optional[Sequence[Callable]] = None,
    eval_train_metric: bool = False,
    return_cvbooster: bool = False,
) -> Dict[str, List[float]]:
    params = dict(params or {})
    if metrics is not None:
        params["metric"] = metrics
    cfg = Config.from_params(params)
    if "num_iterations" in {Config.canonical_name(k) for k in params}:
        num_boost_round = cfg.num_iterations
    train_set.construct()
    if stratified and cfg.objective not in (
            "binary", "multiclass", "multiclassova"):
        stratified = False

    if folds is None:
        folds = _make_n_folds(train_set, nfold, params, seed, stratified,
                              shuffle)
    cvbooster = CVBooster()
    fold_data = []
    for train_idx, test_idx in folds:
        dtrain = train_set.subset(train_idx)
        dtest = train_set.subset(test_idx)
        b = Booster(params=params, train_set=dtrain)
        b.add_valid(dtest, "valid")
        cvbooster.append(b)
        fold_data.append((dtrain, dtest))

    results: Dict[str, List[float]] = {}
    cbs = list(callbacks or [])
    es_rounds = cfg.early_stopping_round
    best_iter = -1
    best_scores = {}
    no_improve = 0
    best_agg = None
    for it in range(num_boost_round):
        agg: Dict[str, List[float]] = {}
        hb_map: Dict[str, bool] = {}
        for b in cvbooster.boosters:
            b.update()
            for ds, name, value, hb in b.eval_valid(feval):
                key = f"{ds} {name}"
                agg.setdefault(key, []).append(value)
                hb_map[key] = hb
            if eval_train_metric:
                for ds, name, value, hb in b.eval_train(feval):
                    key = f"train {name}"
                    agg.setdefault(key, []).append(value)
                    hb_map[key] = hb
        for key, vals in agg.items():
            results.setdefault(f"{key}-mean", []).append(float(np.mean(vals)))
            results.setdefault(f"{key}-stdv", []).append(float(np.std(vals)))
        if es_rounds and es_rounds > 0 and agg:
            key0 = next(iter(agg))
            mean0 = results[f"{key0}-mean"][-1]
            better = (best_agg is None
                      or (mean0 > best_agg if hb_map[key0] else mean0 < best_agg))
            if better:
                best_agg, best_iter, no_improve = mean0, it + 1, 0
            else:
                no_improve += 1
                if no_improve >= es_rounds:
                    cvbooster.best_iteration = best_iter
                    for k in list(results):
                        results[k] = results[k][:best_iter]
                    break
    if return_cvbooster:
        results["cvbooster"] = cvbooster
    return results
