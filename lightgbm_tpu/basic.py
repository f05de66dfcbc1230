"""User-facing Dataset and Booster.

Reference: python-package/lightgbm/basic.py (Dataset :1194, Booster :2705).
Unlike the reference there is no ctypes/C-API hop: Dataset wraps the host
binning layer directly and Booster wraps the device boosting loop.  The
public surface (constructor signatures, lazy construction with
``reference=``, ``free_raw_data``, update/rollback/eval/predict/save)
mirrors the reference so downstream code ports by changing the import.
"""
from __future__ import annotations

import io as _io
import json
from pathlib import Path
import abc
from typing import Any, Dict, List, Optional, Union

import numpy as np

from .config import Config
from .io.dataset_core import BinnedDataset, Metadata
from .metric import create_metrics
from .models import create_boosting
from .models.model_text import (dump_model_to_json, feature_importance,
                                load_model_from_string, save_model_to_string)
from .objective import create_objective
# import-time binding (the engine.py purge/reimport convention): a
# booster must fire/track injected faults in ITS OWN generation's
# one-shot store, not the newest import's
from .resilience import faults as resilience_faults
from .utils import log

__all__ = ["Dataset", "Booster", "Sequence", "LightGBMError"]

from .utils.log import LightGBMError


class Sequence(abc.ABC):
    """Generic row-access interface for streaming Dataset construction.

    Reference: ``lightgbm.Sequence`` (python-package basic.py) over the
    C-API streaming push (c_api.h:175-278 ``LGBM_DatasetPushRows*``).
    Subclass with ``__getitem__`` (int -> 1-D row, slice -> 2-D rows) and
    ``__len__``; set ``batch_size`` to tune the streaming chunk size.
    Pass one Sequence (or a list of them) as ``Dataset(data=...)`` — the
    full float matrix is never materialised in memory.
    """

    batch_size: int = 4096

    @abc.abstractmethod
    def __getitem__(self, idx):
        raise NotImplementedError

    @abc.abstractmethod
    def __len__(self) -> int:
        raise NotImplementedError


# warn-once for the sparse-predict densify (cleared between runs via
# obs.counters.on_reset, like the routing warn-once caches)
_DENSIFY_WARNED: set = set()


def _note_predict_densify(shape) -> None:
    """The predict path walks raw feature values row-wise, so scipy
    sparse input densifies (ISSUE-14 satellite: the cost used to be
    silent).  One structured ``predict_densify`` obs event per call +
    a warn-once naming the materialized bytes."""
    from .obs.counters import events
    events.record("predict_densify")
    if "predict_densify" in _DENSIFY_WARNED:
        return
    _DENSIFY_WARNED.add("predict_densify")
    rows, cols = (int(shape[0]), int(shape[1])) if len(shape) == 2 \
        else (0, 0)
    log.warning(
        "predict: sparse input densifies to float64 (~%.1f MB for "
        "this %dx%d chunk) — prediction walks raw feature values "
        "row-wise; pass dense float32 rows to avoid the copy (see "
        "README 'Serving': supported predict input types)",
        rows * cols * 8 / 1e6, rows, cols)


def _register_densify_reset() -> None:
    from .obs.counters import on_reset
    on_reset(_DENSIFY_WARNED.clear)


_register_densify_reset()


def _to_numpy_2d(data):
    if hasattr(data, "toarray") and not isinstance(data, np.ndarray):
        # scipy sparse (predict path): densify — prediction walks raw
        # feature values row-wise.  Loud + counted since ISSUE 14.
        _note_predict_densify(getattr(data, "shape", ()))
        return np.asarray(data.toarray(), dtype=np.float64), None, None
    import pandas as pd
    if isinstance(data, pd.DataFrame):
        names = [str(c) for c in data.columns]
        cat_idx = [i for i, c in enumerate(data.columns)
                   if str(data.dtypes.iloc[i]) == "category"]
        arr = data.copy()
        for i in cat_idx:
            arr.isetitem(i, arr.iloc[:, i].cat.codes.replace(-1, np.nan))
        return arr.to_numpy(dtype=np.float64, na_value=np.nan), names, cat_idx
    if isinstance(data, (str, Path)):
        from .io.loader import load_text_file
        arr, _label, _w, _g = load_text_file(str(data))
        return arr, None, None
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr, None, None


class Dataset:
    """Training data wrapper (reference basic.py:1194)."""

    def __init__(
        self,
        data,
        label=None,
        reference: Optional["Dataset"] = None,
        weight=None,
        group=None,
        init_score=None,
        feature_name: Union[str, List[str]] = "auto",
        categorical_feature: Union[str, List] = "auto",
        params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = True,
    ):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params) if params else {}
        self.free_raw_data = free_raw_data
        self._binned: Optional[BinnedDataset] = None
        self.used_indices = None

    # ------------------------------------------------------------------
    def _update_params(self, params: Optional[Dict[str, Any]]) -> "Dataset":
        if params:
            for k, v in params.items():
                self.params.setdefault(k, v)
        return self

    def construct(self) -> "Dataset":
        if self._binned is not None:
            return self
        cfg = Config.from_params(self.params)
        data = self.data
        label, weight, group, init_score = (
            self.label, self.weight, self.group, self.init_score)

        seqs = None
        if isinstance(data, Sequence):
            seqs = [data]
        elif (isinstance(data, list) and data
              and all(isinstance(s, Sequence) for s in data)):
            seqs = data

        if seqs is not None:
            names, cat_idx = None, None
        elif isinstance(data, (str, Path)):
            path = str(data)
            if path.endswith(".bin") or path.endswith(".npz"):
                self._binned = BinnedDataset.load_binary(path)
                return self
            from .io.loader import load_text_file
            arr, file_label, file_weight, file_group = load_text_file(
                path, config=cfg)
            data = arr
            label = label if label is not None else file_label
            weight = weight if weight is not None else file_weight
            group = group if group is not None else file_group
            names, cat_idx = None, None
        elif hasattr(data, "tocsc") and not isinstance(data, np.ndarray):
            names, cat_idx = None, None   # scipy sparse: binned column-wise
        else:
            data, names, cat_idx = _to_numpy_2d(data)

        feature_names = None
        if isinstance(self.feature_name, (list, tuple)):
            feature_names = [str(s) for s in self.feature_name]
        elif names is not None:
            feature_names = names

        categorical_indices = None
        if isinstance(self.categorical_feature, (list, tuple)):
            categorical_indices = []
            for c in self.categorical_feature:
                if isinstance(c, (int, np.integer)):
                    categorical_indices.append(int(c))
                elif feature_names and c in feature_names:
                    categorical_indices.append(feature_names.index(c))
                else:
                    log.warning("Unknown categorical feature %s", c)
        elif cat_idx:
            categorical_indices = cat_idx
        elif cfg.categorical_feature:
            categorical_indices = [
                int(x) for x in str(cfg.categorical_feature).split(",")
                if x.strip().lstrip("-").isdigit()]

        ref = self.reference.construct()._binned if self.reference is not None else None
        if seqs is not None:
            self._binned = BinnedDataset.construct_from_sequences(
                seqs, cfg,
                label=label, weight=weight, group=group,
                init_score=init_score, feature_names=feature_names,
                categorical_indices=categorical_indices, reference=ref,
            )
        else:
            self._binned = BinnedDataset.construct(
                data, cfg,
                label=label, weight=weight, group=group,
                init_score=init_score, feature_names=feature_names,
                categorical_indices=categorical_indices,
                reference=ref,
            )
        if self.free_raw_data:
            self.data = None
        return self

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, params=params or self.params)

    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._binned is not None:
            self._binned.metadata.set_label(label)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._binned is not None:
            self._binned.metadata.set_weight(weight)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._binned is not None:
            self._binned.metadata.set_group(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._binned is not None:
            self._binned.metadata.set_init_score(init_score)
        return self

    def get_label(self):
        if self._binned is not None:
            return self._binned.metadata.label
        return self.label

    def get_weight(self):
        if self._binned is not None:
            return self._binned.metadata.weight
        return self.weight

    def get_group(self):
        if self._binned is not None and self._binned.metadata.query_boundaries is not None:
            return np.diff(self._binned.metadata.query_boundaries)
        return self.group

    def get_init_score(self):
        return self.init_score

    def num_data(self) -> int:
        self.construct()
        return self._binned.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._binned.num_total_features

    def get_feature_name(self) -> List[str]:
        self.construct()
        return self._binned.feature_names

    def subset(self, used_indices, params=None) -> "Dataset":
        self.construct()
        d = Dataset.__new__(Dataset)
        d.__dict__.update(self.__dict__)
        d._binned = self._binned.subset(np.asarray(used_indices))
        d.used_indices = used_indices
        return d

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()
        self._binned.save_binary(str(filename))
        return self

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Dataset::AddFeaturesFrom analog: horizontal concat."""
        self.construct()
        other.construct()
        a, b = self._binned, other._binned
        if a.num_data != b.num_data:
            log.fatal("Cannot add features from dataset with different num_data")
        a.bin_matrix = np.concatenate([a.bin_matrix, b.bin_matrix], axis=1)
        a.mappers = a.mappers + b.mappers
        a.used_feature_map = np.concatenate(
            [a.used_feature_map, b.used_feature_map + a.num_total_features])
        a.feature_names = a.feature_names + b.feature_names
        a.num_total_features += b.num_total_features
        return self


class Booster:
    """Training/prediction handle (reference basic.py:2705)."""

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        train_set: Optional[Dataset] = None,
        model_file: Optional[str] = None,
        model_str: Optional[str] = None,
    ):
        self.params = dict(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict = {}
        # iteration engine.train restored from a ckpt/v1 snapshot
        # (0 = started fresh; ISSUE 13)
        self.resumed_from = 0
        self._loaded = None
        self._inner = None
        self.train_set = train_set
        self._name_valid_sets: List[str] = []
        self._train_data_name = "training"

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance")
            train_set._update_params(self.params).construct()
            cfg = Config.from_params(self.params)
            objective = create_objective(cfg)
            metrics = (create_metrics(cfg)
                       if cfg.is_provide_training_metric else [])
            if objective is not None:
                objective.init(train_set._binned.metadata,
                               train_set._binned.num_data)
            self._inner = create_boosting(cfg, train_set._binned, objective,
                                          metrics)
            self.config = cfg
        elif model_file is not None:
            with open(model_file) as f:
                self._load(f.read())
        elif model_str is not None:
            self._load(model_str)
        else:
            raise TypeError("Need at least one training dataset or model "
                            "file or model string to create Booster instance")

    # ------------------------------------------------------------------
    def _load(self, text: str) -> None:
        self._loaded = load_model_from_string(text)
        self.config = Config.from_params(
            {k: v for k, v in self._loaded.params.items()})
        self.best_iteration = -1

    @property
    def _models(self):
        if self._inner is not None:
            self._inner._flush_pending()
            return self._inner.models
        return self._loaded.models

    @property
    def _k(self) -> int:
        if self._inner is not None:
            return self._inner.num_tree_per_iteration
        return self._loaded.num_tree_per_iteration

    @property
    def _average_output(self) -> bool:
        if self._inner is not None:
            return self._inner.average_output
        return self._loaded.average_output

    @property
    def _objective_str(self) -> str:
        if self._inner is not None and self._inner.objective is not None:
            return str(self._inner.objective)
        if self._loaded is not None:
            return self._loaded.objective_str
        return ""

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if self._inner is None:
            raise LightGBMError("Cannot add validation data to loaded model")
        if data.reference is None and data._binned is None:
            # valid sets must share the training bin mappers or their
            # bin-space replay is silently meaningless (the reference's
            # basic.py enforces the same via Dataset.set_reference)
            data.reference = self.train_set
        data._update_params(self.params).construct()
        metrics = create_metrics(self.config)
        self._inner.add_valid(data._binned, name, metrics)
        self._name_valid_sets.append(name)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; True when training should stop
        (reference Booster.update / LGBM_BoosterUpdateOneIter)."""
        if self._inner is None:
            raise LightGBMError("Cannot update a loaded model")
        if train_set is not None:
            raise LightGBMError("Resetting train set on an existing booster "
                                "is not supported yet")
        # fault injection (ISSUE 13): LGBM_TPU_FAULT=<class>@<iter>
        # fires HERE — the one boundary every training driver
        # (engine.train, bench.py, cv folds) goes through.  Off (the
        # default) is a cached no-op.
        resilience_faults.maybe_fire(self._inner.iter_)
        if fobj is not None:
            grad, hess = fobj(self._predict_for_fobj(), self.train_set)
            grad = np.asarray(grad, np.float32)
            hess = np.asarray(hess, np.float32)
            k, n = self._k, self.train_set._binned.num_data
            if grad.ndim == 2:  # [n, K] -> [K, n]
                grad, hess = grad.T, hess.T
            return self._inner.train_one_iter(grad.reshape(k, n),
                                              hess.reshape(k, n))
        return self._inner.train_one_iter()

    def _predict_for_fobj(self):
        # train_score is padded to the device row layout; the custom
        # objective sees exactly num_data rows
        score = np.asarray(self._inner.get_training_score(), np.float64)
        score = score[:, :self.train_set._binned.num_data]
        return score[0] if self._k == 1 else score.T

    def rollback_one_iter(self) -> "Booster":
        self._inner.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        if self._inner is not None:
            return self._inner.current_iteration()
        return len(self._loaded.models) // self._k

    def num_trees(self) -> int:
        # length-only: deferred placeholders keep the list aligned, so no
        # flush (a flush here would force a device sync mid-training)
        if self._inner is not None:
            return len(self._inner.models)
        return len(self._loaded.models)

    def num_model_per_iteration(self) -> int:
        return self._k

    def num_feature(self) -> int:
        if self._inner is not None:
            return self._inner.train_set.num_total_features
        return self._loaded.max_feature_idx + 1

    def feature_name(self) -> List[str]:
        if self._inner is not None:
            return self._inner.train_set.feature_names
        return self._loaded.feature_names

    # ------------------------------------------------------------------
    def eval_train(self, feval=None) -> List:
        return self._eval("training", feval)

    def eval_valid(self, feval=None) -> List:
        out = []
        for name in self._name_valid_sets:
            out.extend(self._eval(name, feval))
        return out

    def _eval(self, dataset_name: str, feval=None) -> List:
        # one data set's metrics, no other's computed
        # (LGBM_BoosterGetEval(data_idx))
        res = list(self._inner.eval(dataset_name))
        if feval is not None:
            res.extend(_run_feval(self, feval, dataset_name))
        return res

    def eval(self, data, name, feval=None) -> List:
        return self._eval(name, feval)

    # ------------------------------------------------------------------
    def predict(
        self,
        data,
        start_iteration: int = 0,
        num_iteration: Optional[int] = None,
        raw_score: bool = False,
        pred_leaf: bool = False,
        pred_contrib: bool = False,
        **kwargs,
    ) -> np.ndarray:
        if isinstance(data, Dataset):
            raise TypeError("Cannot use Dataset instance for prediction, "
                            "please use raw data instead")
        if hasattr(data, "tocsr") and not isinstance(data, np.ndarray):
            # scipy sparse: densify in row chunks so a huge sparse matrix
            # never materialises whole (~128 MB of float64 per chunk)
            csr = data.tocsr()
            n_rows, n_cols = csr.shape
            chunk = max(1, (1 << 24) // max(n_cols, 1))
            if n_rows > chunk:
                outs = [self.predict(
                            csr[i:i + chunk], start_iteration=start_iteration,
                            num_iteration=num_iteration, raw_score=raw_score,
                            pred_leaf=pred_leaf, pred_contrib=pred_contrib,
                            **kwargs)
                        for i in range(0, n_rows, chunk)]
                return np.concatenate(outs, axis=0)
        arr, _, _ = _to_numpy_2d(data)
        models = self._models
        k = self._k
        total_iter = len(models) // max(k, 1)
        if num_iteration is None or num_iteration < 0:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else total_iter)
        end = min(start_iteration + num_iteration, total_iter)
        early_stop = bool(kwargs.get("pred_early_stop", False))

        # ISSUE 14: compiled-serve vs host-walk routing.  The decision
        # is a named-rule table (ops/routing.py predict_decide) shared
        # with the golden matrix; config-caused host fallbacks record
        # routing_fallback_predict_* events.
        from .ops import routing as routing_mod
        decision = self._predict_route(
            routing_mod, models, pred_leaf=pred_leaf,
            pred_contrib=pred_contrib, early_stop=early_stop)
        routing_mod.report_predict_fallbacks(decision)

        if pred_leaf:
            out = np.zeros((arr.shape[0], (end - start_iteration) * k), np.int32)
            for it in range(start_iteration, end):
                for kk in range(k):
                    t = models[it * k + kk]
                    out[:, (it - start_iteration) * k + kk] = t.predict_leaf(arr)
            return out
        if pred_contrib:
            return self._predict_contrib(arr, start_iteration, end)

        if decision.path == "compiled":
            raw = self._serve_raw(arr, start_iteration, end)
        else:
            raw = np.zeros((k, arr.shape[0]), np.float64)
            # prediction early stopping (reference predictor.hpp:41-59 /
            # CreatePredictionEarlyStopInstance): every `freq` iterations,
            # rows whose margin already exceeds the threshold stop
            # accumulating trees.  Margin = |score| for binary, top1-top2
            # for multiclass.
            es_freq = max(int(kwargs.get("pred_early_stop_freq", 10)), 1)
            es_margin = float(kwargs.get("pred_early_stop_margin", 1e10))
            active = np.ones(arr.shape[0], bool)
            for it in range(start_iteration, end):
                for kk in range(k):
                    if early_stop and not active.all():
                        raw[kk, active] += models[it * k + kk].predict(
                            arr[active])
                    else:
                        raw[kk] += models[it * k + kk].predict(arr)
                if early_stop and (it - start_iteration + 1) % es_freq == 0:
                    if k == 1:
                        # reference binary margin is 2*|score|
                        # (pred_early_stop.cpp MarginBinary)
                        margin = 2.0 * np.abs(raw[0])
                    else:
                        top2 = np.sort(raw, axis=0)[-2:]
                        margin = top2[1] - top2[0]
                    active &= margin < es_margin
                    if not active.any():
                        break
        if self._average_output:
            raw /= max(end - start_iteration, 1)
        if raw_score:
            return raw[0] if k == 1 else raw.T
        conv = _convert_output_np(raw, self._objective_str)
        return conv[0] if k == 1 and conv.ndim == 2 else conv.T if conv.ndim == 2 else conv

    def _predict_contrib(self, arr, start, end) -> np.ndarray:
        if any(getattr(t, "is_linear", False) for t in self._models):
            raise LightGBMError(
                "pred_contrib is not supported for linear trees")
        from .models.shap import predict_contrib
        return predict_contrib(self, arr, start, end)

    # -- compiled serving (ISSUE 14) -----------------------------------
    def _predict_route(self, routing_mod, models, *, pred_leaf: bool,
                       pred_contrib: bool, early_stop: bool):
        import jax

        from .serve.model import kernel_fit_probe
        return routing_mod.predict_decide(routing_mod.PredictInputs(
            backend=jax.default_backend(),
            serve_env=routing_mod.predict_env_snapshot(),
            loaded_model=self._inner is None,
            rebinned_model=any(getattr(t, "rebinned", False)
                               for t in models),
            linear_tree=any(getattr(t, "is_linear", False)
                            for t in models),
            pred_contrib=pred_contrib, pred_leaf=pred_leaf,
            pred_early_stop=early_stop,
            serve_kernel_env=routing_mod.predict_kernel_env_snapshot(),
            forest_overwide=not kernel_fit_probe(models)))

    def serving_engine(self, start_iteration: int = 0,
                       end_iteration: Optional[int] = None):
        """The cached compiled serving engine for an iteration slice
        (built on first use; keyed by slice + current tree count so a
        booster that trains further recompiles the stack).  The bulk
        path and latency queue are also usable directly:
        ``ServingQueue(booster.serving_engine())``."""
        models = self._models
        k = self._k
        total_iter = len(models) // max(k, 1)
        end = total_iter if end_iteration is None \
            else min(int(end_iteration), total_iter)
        key = (int(start_iteration), end, len(models))
        cache = self.__dict__.setdefault("_serve_engines", {})
        # evict engines stacked against an earlier tree count: the
        # booster can never dispatch through them again, and a
        # train/predict loop would otherwise pin one full stacked
        # forest in device memory per iteration
        for stale in [k_ for k_ in cache if k_[2] != len(models)]:
            del cache[stale]
        eng = cache.get(key)
        if eng is not None:
            cache[key] = cache.pop(key)   # LRU: mark most-recent
        if eng is None:
            from .serve import ServingEngine, ServingModel
            sm = ServingModel.from_booster(
                self, start_iteration=start_iteration,
                end_iteration=end)
            eng = ServingEngine(sm)
            cache[key] = eng
            # bound the per-slice cache: a num_iteration sweep over a
            # fixed booster would otherwise pin one stacked forest on
            # device per slice (O(T^2) tree copies); LRU keeps the few
            # slices a serving process actually rotates between
            while len(cache) > 4:
                del cache[next(iter(cache))]
            if self._inner is not None:
                # routing_info() reports the serving digest from here on
                self._inner.note_serving(sm.to_json())
        return eng

    def _serve_raw(self, arr, start, end) -> np.ndarray:
        """Compiled-forest raw scores, in the host path's [k, n] f64
        layout so the conversion tail is shared.  Inputs are cast to
        f32 (the serving contract — README 'Supported predict input
        types'): a value beyond f32 precision may land one bin away
        from the f64 host walk."""
        eng = self.serving_engine(start, end)
        scores = eng.predict(np.asarray(arr, np.float32))   # [n, K]
        return np.asarray(scores, np.float64).T

    # ------------------------------------------------------------------
    def refit(self, data, label, weight=None, decay_rate: float = 0.9,
              **kwargs) -> "Booster":
        """Refit leaf values on new data keeping every tree's structure.

        Reference: GBDT::RefitTree (gbdt.cpp) driven by the CLI ``task=refit``
        (application.cpp:221-248) and Booster.refit (basic.py): per
        iteration, gradients at the current refitted score decide new leaf
        outputs; ``decay_rate`` blends old and new values.
        """
        from .io.dataset_core import Metadata
        from .objective import create_objective

        X, _, _ = _to_numpy_2d(data)
        X = np.asarray(X, np.float64)
        y = np.asarray(label, np.float64).reshape(-1)
        n = X.shape[0]

        new_b = Booster(model_str=self.model_to_string())
        models = new_b._models
        k = new_b._k
        cfg = Config.from_params({**(self.params or {}), **kwargs})
        obj_str = (self._loaded.objective_str if self._loaded is not None
                   else str(self._inner.objective))
        if obj_str and not cfg.objective:
            cfg.objective = obj_str.split(" ")[0]
        objective = create_objective(cfg)
        if objective is None:
            log.fatal("refit requires a model with an objective")
        md = Metadata()
        md.set_label(y)
        if weight is not None:
            md.set_weight(np.asarray(weight, np.float64))
        objective.init(md, n)
        l1, l2 = cfg.lambda_l1, cfg.lambda_l2

        import jax.numpy as jnp
        score = np.zeros((k, n), np.float64)
        n_iters = len(models) // k
        for it in range(n_iters):
            s = jnp.asarray(score, jnp.float32)
            g, h = objective.get_gradients(s if k > 1 else s[0])
            g = np.asarray(g, np.float64).reshape(k, n)
            h = np.asarray(h, np.float64).reshape(k, n)
            for c in range(k):
                tree = models[it * k + c]
                leaf_idx = tree.predict_leaf(X)
                nl = tree.num_leaves
                sg = np.bincount(leaf_idx, weights=g[c], minlength=nl)
                sh = np.bincount(leaf_idx, weights=h[c], minlength=nl)
                sg_t = np.sign(sg) * np.maximum(np.abs(sg) - l1, 0.0)
                new_out = -sg_t / (sh + l2 + 1e-38) * tree.shrinkage
                tree.leaf_value = (decay_rate * tree.leaf_value
                                   + (1.0 - decay_rate) * new_out)
                score[c] += tree.leaf_value[leaf_idx]
        return new_b

    # ------------------------------------------------------------------
    def save_model(self, filename, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        with open(filename, "w") as f:
            f.write(self.model_to_string(num_iteration, start_iteration,
                                         importance_type))
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        imp = 0 if importance_type == "split" else 1
        if self._inner is not None:
            return save_model_to_string(self._inner, start_iteration,
                                        num_iteration, imp)
        return save_model_to_string(_LoadedAsBooster(self._loaded),
                                    start_iteration, num_iteration, imp)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> dict:
        target = (self._inner if self._inner is not None
                  else _LoadedAsBooster(self._loaded))
        return dump_model_to_json(target, start_iteration,
                                  num_iteration or -1)

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        imp = 0 if importance_type == "split" else 1
        target = (self._inner if self._inner is not None
                  else _LoadedAsBooster(self._loaded))
        out = feature_importance(target, iteration or -1, imp)
        return out if imp else out.astype(np.int32)

    def free_dataset(self) -> "Booster":
        return self

    def free_network(self) -> "Booster":
        """Reference LGBM_BoosterFreeNetwork: tear down the multi-host
        process group (Network::Dispose)."""
        from .parallel.network import Network
        Network.dispose()
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        self._train_data_name = name
        return self


class _LoadedAsBooster:
    """Adapter so model_text functions accept a LoadedModel."""

    def __init__(self, loaded):
        self.models = loaded.models
        self.config = Config()
        self.config.num_class = loaded.num_class
        self.num_tree_per_iteration = loaded.num_tree_per_iteration
        self.train_set = None
        self.objective = loaded.objective_str or None
        self.average_output = loaded.average_output
        self.feature_names = loaded.feature_names
        self.feature_infos = loaded.feature_infos
        self.max_feature_idx = loaded.max_feature_idx
        self.NAME = loaded.boosting_type


def _convert_output_np(raw: np.ndarray, objective_str: str) -> np.ndarray:
    """Numpy analog of ObjectiveFunction::ConvertOutput keyed off the model's
    objective string (for loaded models)."""
    obj = objective_str.split(" ")[0] if objective_str else ""
    if obj in ("binary", "cross_entropy", "multiclassova"):
        sigmoid = 1.0
        for tok in objective_str.split():
            if tok.startswith("sigmoid:"):
                sigmoid = float(tok.split(":")[1])
        return 1.0 / (1.0 + np.exp(-sigmoid * raw))
    if obj == "multiclass":
        e = np.exp(raw - raw.max(axis=0, keepdims=True))
        return e / e.sum(axis=0, keepdims=True)
    if obj in ("poisson", "gamma", "tweedie"):
        return np.exp(raw)
    if obj == "cross_entropy_lambda":
        return np.log1p(np.exp(raw))
    if "sqrt" in objective_str:
        return np.sign(raw) * raw * raw
    return raw


def _run_feval(booster: Booster, feval, dataset_name: str) -> List:
    # custom eval functions receive (preds, eval_data)
    out = []
    fevals = feval if isinstance(feval, (list, tuple)) else [feval]
    inner = booster._inner
    # (the train score is read only when it is asked for: on the stream
    # route a read runs a program, models/gbdt.py ``train_score``)
    datasets = {vs.name: (vs.score, vs.data) for vs in inner.valid_sets}
    if dataset_name == "training":
        score, bds = inner.train_score, inner.train_set
    elif dataset_name in datasets:
        score, bds = datasets[dataset_name]
    else:
        return out
    prob, raw_s = inner._converted_scores(score)
    # scores are padded to the device row layout; feval sees num_data rows
    prob = np.asarray(prob)[..., :bds.num_data]
    preds = prob if booster._k == 1 else prob.T

    class _EvalData:
        pass

    ed = _EvalData()
    ed.label = bds.metadata.label
    ed.get_label = lambda: bds.metadata.label
    ed.get_weight = lambda: bds.metadata.weight
    ed.get_group = lambda: (
        None if bds.metadata.query_boundaries is None
        else np.diff(bds.metadata.query_boundaries))
    for f in fevals:
        res = f(preds, ed)
        if isinstance(res, tuple):
            res = [res]
        for name, value, hb in res:
            out.append((dataset_name, name, value, hb))
    return out
