"""Deterministic checkpoint/resume for the boosting loop (ISSUE 13
tentpole piece 1, schema ``lightgbm_tpu/ckpt/v1``).

Reference LightGBM survives interruption by design — continued
training from a saved model is a first-class path (``boosting.h``
model save/restore + ``init_model``) — but the ``init_model`` route
re-derives scores through the host prediction path, which is NOT
bit-identical to the running f32 score vector, so a killed run that
"resumed" through it would grow different trees.  A ckpt/v1 snapshot
therefore captures the exact boosting state:

* the **forest** as the existing model text (``models/model_text.py``,
  ``%.17g`` floats — an exact f64 round trip);
* the **score vector** as raw f32 ``.npy`` bytes (plus its sha256 in
  the manifest) — restoring it verbatim is what makes
  kill-at-iteration-i + resume grow BYTE-IDENTICAL trees vs the
  uninterrupted run;
* **RNG state**: the feature-fraction and bagging ``PCG64`` states
  (bagging masks and GOSS keys are stateless functions of
  seed x iteration, so the mid-cycle bagging cache is re-derived at
  restore);
* the **iteration counter**, per-class need-train flags, shrinkage
  rate, and the CEGB paid-rows mask when engaged;
* a **routing digest** (``ops/routing.py``), a **config
  fingerprint**, and a **data digest** (binned matrix + labels): a
  resume whose freshly-built booster disagrees on any of them REFUSES
  with a structured finding (exit 2 at the CLI layer, mirroring
  ``obs diff``'s incomparable-records semantics) — resuming a stream
  checkpoint on a row_order path, or onto a refreshed same-shape
  dataset, would not reproduce the run, it would silently fork it.

Writes are atomic (tmp dir + rename, then the ``LATEST`` pointer is
replaced last), so a kill mid-write leaves the previous checkpoint
authoritative and the torn one unreferenced; every load re-verifies
the payload digests, so torn or bit-rotted checkpoints surface as
:class:`CheckpointError` (classified ``checkpoint_corrupt``), never a
garbage resume.

Layout of one checkpoint::

    <dir>/ckpt_000010/manifest.json   # schema, digests, rng, counters
    <dir>/ckpt_000010/model.txt       # forest (model_text format)
    <dir>/ckpt_000010/score.npy       # [K, n_pad] f32 train scores
    <dir>/ckpt_000010/cegb_paid.npy   # optional CEGB mask
    <dir>/LATEST                      # name of the newest complete ckpt

The checked-in golden fixture ``tests/data/ckpt_r01`` (regenerated by
``python -m lightgbm_tpu.resilience``) pins this format: resuming from
it must keep producing byte-identical trees forever.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

from ..config import Config, env_knob
from ..obs import findings as F
from ..utils import log

CKPT_SCHEMA = "lightgbm_tpu/ckpt/v1"
CKPT_DIR_ENV = "LGBM_TPU_CKPT_DIR"
CKPT_EVERY_ENV = "LGBM_TPU_CKPT_EVERY"
CKPT_KEEP_ENV = "LGBM_TPU_CKPT_KEEP"

# config fields that do not influence the trained trees — a resume must
# not be refused because the operator changed logging chattiness
_FINGERPRINT_EXEMPT = ("verbosity", "metric_freq", "snapshot_freq",
                       "is_provide_training_metric", "output_model",
                       "input_model", "save_binary")

_BOOSTINGS_SUPPORTED = ("gbdt", "goss")


class CheckpointError(Exception):
    """A checkpoint exists but cannot be consumed (truncated manifest,
    payload digest mismatch, dangling LATEST pointer).  Carries a
    structured finding; CLIs exit 2 — never a traceback."""

    exit_code = F.EXIT_UNUSABLE

    def __init__(self, message: str, **detail: Any):
        super().__init__(message)
        self.finding = F.make_finding(
            "ckpt", "CKPT_CORRUPT", f"checkpoint corrupt: {message}",
            **detail)


class ResumeRefused(Exception):
    """The checkpoint is valid but belongs to a DIFFERENT run: the
    freshly-built booster disagrees on the config fingerprint or the
    engaged routing digest.  Resuming would silently fork the run, so
    refuse with a structured finding (exit 2 at the CLI layer)."""

    exit_code = F.EXIT_UNUSABLE

    def __init__(self, code: str, message: str, **detail: Any):
        super().__init__(message)
        self.finding = F.make_finding("ckpt", code, message, **detail)


class CkptPolicy(NamedTuple):
    dir: Optional[str]
    every: int
    keep: int


def policy_from_env(environ=None, *,
                    default_dir: Optional[str] = None) -> CkptPolicy:
    """The engine's checkpoint policy from the registered knobs:
    ``LGBM_TPU_CKPT_DIR`` (off = disabled), ``_EVERY`` (iterations
    between saves; 0 = resume-only), ``_KEEP`` (retained count).
    ``default_dir`` substitutes for an unset/off dir knob (bench
    ``--resume`` asks for checkpointing explicitly, so it supplies a
    default instead of disabling)."""
    d = env_knob(CKPT_DIR_ENV, environ).strip()
    if d.lower() in ("", "off", "0"):
        if default_dir is None:
            return CkptPolicy(None, 0, 0)
        d = default_dir
    try:
        every = int(env_knob(CKPT_EVERY_ENV, environ))
        keep = int(env_knob(CKPT_KEEP_ENV, environ))
    except ValueError as e:
        raise ValueError(
            f"{CKPT_EVERY_ENV}/{CKPT_KEEP_ENV} must be integers: {e}")
    return CkptPolicy(d, max(every, 0), max(keep, 1))


def supports(inner) -> Optional[str]:
    """None when the booster can checkpoint, else the named reason it
    cannot (the engine warns once and trains unprotected)."""
    if inner.NAME not in _BOOSTINGS_SUPPORTED:
        return (f"boosting={inner.NAME} carries per-iteration state "
                "the ckpt/v1 snapshot does not capture")
    if getattr(inner, "_pre_part", False):
        return ("pre-partitioned multi-process training holds only "
                "local score rows per rank")
    return None


# ---------------------------------------------------------------------
# digests
# ---------------------------------------------------------------------
def config_fingerprint(cfg: Config) -> str:
    """Stable digest of every training-semantic config field.  A
    resume across fingerprints would grow different trees from the
    restored state, so it refuses instead."""
    d = dataclasses.asdict(cfg)
    for k in _FINGERPRINT_EXEMPT:
        d.pop(k, None)
    payload = json.dumps(d, sort_keys=True, default=str)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


_DATA_DIGEST_CAP = 64 << 20   # bytes of bin matrix hashed (strided above)


def data_fingerprint(inner) -> Optional[str]:
    """Digest of the TRAINING DATA a snapshot's trees were grown on
    (binned matrix + labels): the config/routing fingerprints cannot
    see an operator reusing a checkpoint dir across a same-shape data
    refresh, and resuming onto different data mixes trees from two
    datasets into one forest.  Matrices beyond ``_DATA_DIGEST_CAP``
    hash a deterministic row stride (plus shape/dtype and the full
    label vector) — a guard against accidental reuse, not a
    cryptographic containment.  Cached on the booster: the data never
    changes mid-run, so each process pays the hash once."""
    cached = getattr(inner, "_ckpt_data_digest", None)
    if cached is not None:
        return cached
    ds = getattr(inner, "train_set", None)
    bm = getattr(ds, "bin_matrix", None)
    if bm is None:
        return None
    h = hashlib.sha256()
    h.update(str(bm.shape).encode())
    h.update(str(bm.dtype).encode())
    if bm.nbytes <= _DATA_DIGEST_CAP:
        h.update(np.ascontiguousarray(bm).tobytes())
    else:
        step = max(1, bm.nbytes // _DATA_DIGEST_CAP)
        h.update(np.ascontiguousarray(bm[::step]).tobytes())
        h.update(np.ascontiguousarray(bm[-1:]).tobytes())
    label = getattr(ds.metadata, "label", None)
    if label is not None:
        lb = np.asarray(label)
        h.update(str(lb.shape).encode())
        h.update(np.ascontiguousarray(lb).tobytes())
    digest = h.hexdigest()[:16]
    inner._ckpt_data_digest = digest
    return digest


def array_digest(a: np.ndarray) -> str:
    a = np.ascontiguousarray(a)
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())
    return h.hexdigest()[:16]


def text_digest(s: str) -> str:
    return hashlib.sha256(s.encode()).hexdigest()[:16]


# ---------------------------------------------------------------------
# save
# ---------------------------------------------------------------------
def save_booster(booster, ckpt_dir: str, *, keep: int = 2,
                 every: int = 0,
                 fingerprint: Optional[str] = None) -> str:
    """Write one complete checkpoint of ``booster``'s boosting state
    under ``ckpt_dir`` and point ``LATEST`` at it (atomically); prunes
    retained checkpoints beyond ``keep``.  ``every`` records the
    engaged cadence in the manifest (the cadence is part of the
    float-exact trajectory — each save re-anchors the physical row
    permutation).  ``fingerprint`` overrides the config fingerprint:
    ``engine.train`` passes the one it computed at train START,
    because a ``reset_parameter`` learning-rate schedule mutates the
    live Config mid-run and a fingerprint of the mutated config would
    refuse every legitimate resume.  Returns the checkpoint path."""
    from ..models.model_text import save_model_to_string
    from ..obs import events as obs_events

    inner = booster._inner
    reason = supports(inner)
    if reason is not None:
        raise ValueError(f"cannot checkpoint: {reason}")
    st = inner.checkpoint_state()
    it = st["iteration"]
    model_text = save_model_to_string(inner, 0, -1, 0)
    routing = getattr(inner, "_routing", None)
    manifest: Dict[str, Any] = {
        "schema": CKPT_SCHEMA,
        "iteration": it,
        "boosting": inner.NAME,
        "num_tree_per_iteration": inner.num_tree_per_iteration,
        "num_trees": len(inner.models),
        "config_fingerprint": (fingerprint
                               or config_fingerprint(booster.config)),
        "data_digest": data_fingerprint(inner),
        "routing_digest": (routing.digest() if routing is not None
                           else None),
        "model_digest": text_digest(model_text),
        "score_digest": array_digest(st["train_score"]),
        "score_shape": list(st["train_score"].shape),
        "rng_feature": st["rng_feature"],
        "rng_bagging": st["rng_bagging"],
        "shrinkage_rate": st["shrinkage_rate"],
        "class_need_train": st["class_need_train"],
        "has_cegb": st["cegb_paid"] is not None,
        "ckpt_every": int(every),
    }
    name = f"ckpt_{it:06d}"
    final = os.path.join(ckpt_dir, name)
    tmp = os.path.join(ckpt_dir, f".{name}.tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    with open(os.path.join(tmp, "model.txt"), "w") as f:
        f.write(model_text)
        f.flush()
        os.fsync(f.fileno())
    np.save(os.path.join(tmp, "score.npy"), st["train_score"])
    if st["cegb_paid"] is not None:
        np.save(os.path.join(tmp, "cegb_paid.npy"), st["cegb_paid"])
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    shutil.rmtree(final, ignore_errors=True)
    os.replace(tmp, final)
    # LATEST flips last: a kill anywhere above leaves the previous
    # checkpoint authoritative and the new dir merely unreferenced
    latest_tmp = os.path.join(ckpt_dir, ".LATEST.tmp")
    with open(latest_tmp, "w") as f:
        f.write(name + "\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(latest_tmp, os.path.join(ckpt_dir, "LATEST"))
    _prune(ckpt_dir, keep=keep)
    # re-anchor the carried physical row permutation: the comb order a
    # resumed process rebuilds from the snapshot must match the order
    # the SURVIVING process continues with (leaf-value float sums
    # accumulate in comb row order — without this a resume is 1 ulp
    # off on the physical paths)
    inner._reanchor_physical()
    obs_events.record("ckpt_save")
    log.info("checkpoint written: %s (iteration %d, %d trees)",
             final, it, manifest["num_trees"])
    return final


def _prune(ckpt_dir: str, *, keep: int) -> None:
    names = sorted(n for n in os.listdir(ckpt_dir)
                   if n.startswith("ckpt_")
                   and os.path.isdir(os.path.join(ckpt_dir, n)))
    for n in names[:-keep] if keep > 0 else []:
        shutil.rmtree(os.path.join(ckpt_dir, n), ignore_errors=True)


# ---------------------------------------------------------------------
# load
# ---------------------------------------------------------------------
@dataclasses.dataclass
class Checkpoint:
    path: str
    manifest: Dict[str, Any]
    model_text: str
    score: np.ndarray
    cegb_paid: Optional[np.ndarray]

    @property
    def iteration(self) -> int:
        return int(self.manifest["iteration"])


def latest(ckpt_dir: str) -> Optional[str]:
    """Path of the newest complete checkpoint under ``ckpt_dir`` (via
    the LATEST pointer), or None when no checkpoint exists.  A LATEST
    naming a missing directory is corruption, not absence."""
    pointer = os.path.join(ckpt_dir, "LATEST")
    if not os.path.exists(pointer):
        return None
    try:
        with open(pointer) as f:
            name = f.read().strip()
    except OSError as e:
        raise CheckpointError(f"LATEST unreadable under "
                              f"{ckpt_dir!r}: {e}")
    if not name or os.sep in name or not name.startswith("ckpt_"):
        raise CheckpointError(
            f"LATEST under {ckpt_dir!r} holds {name!r}, not a "
            "checkpoint name")
    path = os.path.join(ckpt_dir, name)
    if not os.path.isdir(path):
        raise CheckpointError(
            f"LATEST points at {name!r} which does not exist under "
            f"{ckpt_dir!r} (torn prune or partial write)")
    return path


def load(path: str) -> Checkpoint:
    """Read + fully verify one checkpoint directory; raises
    :class:`CheckpointError` on anything torn, truncated or
    bit-rotted."""
    mpath = os.path.join(path, "manifest.json")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except OSError as e:
        raise CheckpointError(f"{mpath}: cannot read: {e}")
    except json.JSONDecodeError as e:
        raise CheckpointError(f"{mpath}: manifest not valid JSON "
                              f"({e}) — partial write")
    if manifest.get("schema") != CKPT_SCHEMA:
        raise CheckpointError(
            f"{mpath}: schema {manifest.get('schema')!r}, expected "
            f"{CKPT_SCHEMA!r}")
    for key in ("iteration", "model_digest", "score_digest",
                "score_shape", "config_fingerprint"):
        if key not in manifest:
            raise CheckpointError(f"{mpath}: manifest missing {key!r}")
    try:
        with open(os.path.join(path, "model.txt")) as f:
            model_text = f.read()
    except OSError as e:
        raise CheckpointError(f"{path}: model.txt unreadable: {e}")
    if text_digest(model_text) != manifest["model_digest"]:
        raise CheckpointError(
            f"{path}: model.txt digest mismatch (truncated or "
            "corrupt forest)")
    try:
        score = np.load(os.path.join(path, "score.npy"))
    except (OSError, ValueError) as e:
        raise CheckpointError(f"{path}: score.npy unreadable: {e}")
    if list(score.shape) != list(manifest["score_shape"]):
        raise CheckpointError(
            f"{path}: score shape {list(score.shape)} != manifest "
            f"{manifest['score_shape']}")
    if array_digest(score) != manifest["score_digest"]:
        raise CheckpointError(
            f"{path}: score digest mismatch (torn write or bit rot)")
    cegb = None
    if manifest.get("has_cegb"):
        try:
            cegb = np.load(os.path.join(path, "cegb_paid.npy"))
        except (OSError, ValueError) as e:
            raise CheckpointError(
                f"{path}: cegb_paid.npy unreadable: {e}")
    return Checkpoint(path=path, manifest=manifest,
                      model_text=model_text, score=score,
                      cegb_paid=cegb)


# ---------------------------------------------------------------------
# restore
# ---------------------------------------------------------------------
def restore(booster, ck: Checkpoint, *,
            fingerprint: Optional[str] = None) -> int:
    """Install a loaded checkpoint into a freshly-built (untrained)
    booster.  Refuses (:class:`ResumeRefused`) when the checkpoint was
    written by a different config or a different engaged routing path —
    continuing would not reproduce the interrupted run.
    ``fingerprint`` overrides the live-config fingerprint (the
    ``save_booster`` contract: engine-computed at train start, immune
    to mid-run ``reset_parameter`` mutation)."""
    from ..models.model_text import load_model_from_string

    inner = booster._inner
    m = ck.manifest
    fp = fingerprint or config_fingerprint(booster.config)
    if m["config_fingerprint"] != fp:
        raise ResumeRefused(
            "RESUME_CONFIG_MISMATCH",
            f"checkpoint {ck.path} was written under config "
            f"fingerprint {m['config_fingerprint']} but this run "
            f"builds {fp} — resuming would fork the run, not "
            "continue it",
            ckpt=m["config_fingerprint"], run=fp)
    ck_data = m.get("data_digest")
    run_data = data_fingerprint(inner)
    if ck_data and run_data and ck_data != run_data:
        raise ResumeRefused(
            "RESUME_DATA_MISMATCH",
            f"checkpoint {ck.path} trained on data digest {ck_data} "
            f"but this run binned {run_data} — its trees belong to a "
            "different dataset, and resuming would mix two datasets' "
            "forests into one model",
            ckpt=ck_data, run=run_data)
    routing = getattr(inner, "_routing", None)
    rd = routing.digest() if routing is not None else None
    if m.get("routing_digest") != rd:
        raise ResumeRefused(
            "RESUME_ROUTING_MISMATCH",
            f"checkpoint {ck.path} trained routing digest "
            f"{m.get('routing_digest')} but this run engaged {rd} — "
            "trees grown on a different path are not a continuation "
            "(obs diff incomparable-records semantics)",
            ckpt=m.get("routing_digest"), run=rd)
    if m.get("boosting") != inner.NAME:
        raise ResumeRefused(
            "RESUME_BOOSTING_MISMATCH",
            f"checkpoint boosting={m.get('boosting')!r} but this run "
            f"builds {inner.NAME!r}")
    loaded = load_model_from_string(ck.model_text)
    if len(loaded.models) != int(m.get("num_trees", -1)):
        raise CheckpointError(
            f"{ck.path}: model.txt holds {len(loaded.models)} trees, "
            f"manifest says {m.get('num_trees')}")
    inner.restore_checkpoint_state(
        loaded.models,
        iteration=ck.iteration,
        train_score=ck.score,
        rng_feature=m.get("rng_feature"),
        rng_bagging=m.get("rng_bagging"),
        shrinkage_rate=m.get("shrinkage_rate"),
        class_need_train=m.get("class_need_train"),
        cegb_paid=ck.cegb_paid,
    )
    return ck.iteration


def maybe_resume(booster, ckpt_dir: str, *,
                 fingerprint: Optional[str] = None,
                 every: Optional[int] = None) -> int:
    """Resume ``booster`` from the latest checkpoint under
    ``ckpt_dir`` if one exists.  Returns the restored iteration (0
    when starting fresh); raises CheckpointError / ResumeRefused per
    their contracts.  ``every`` is the cadence THIS run will write at
    (the caller's resolved policy — bench's default-dir policy is not
    reconstructable from the env alone); it is compared against the
    snapshot's recorded cadence to warn on a byte-identity-breaking
    change."""
    from ..obs import events as obs_events

    path = latest(ckpt_dir)
    if path is None:
        return 0
    ck = load(path)
    it = restore(booster, ck, fingerprint=fingerprint)
    saved_every = ck.manifest.get("ckpt_every")
    if saved_every:
        cur = policy_from_env().every if every is None else every
        if cur != saved_every:
            log.warning(
                "checkpoint cadence changed (snapshot wrote every=%d, "
                "this run writes every=%d): training stays "
                "deterministic, but the trees will not be "
                "byte-identical to an uninterrupted every=%d run — "
                "each save re-anchors the physical row permutation",
                saved_every, cur, saved_every)
    obs_events.record("ckpt_resume")
    log.info("resumed from checkpoint %s (iteration %d, %d trees)",
             path, it, ck.manifest.get("num_trees", -1))
    return it


def render_refusal(exc: Exception) -> List[str]:
    """The uniform finding lines for a CheckpointError/ResumeRefused
    (CLI layers print these and exit 2)."""
    finding = getattr(exc, "finding", None)
    if finding is None:
        finding = F.make_finding("ckpt", "CKPT_ERROR", str(exc))
    return F.render([finding])
