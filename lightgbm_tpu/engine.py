"""Training/CV entry points.

Re-design of /root/reference/python-package/lightgbm/engine.py:
``train`` (:109, iteration loop :309-322), ``cv`` (:625), ``CVBooster``
(:354). Callback ordering, early-stopping unwinding and best_iteration
bookkeeping match the reference semantics.
"""

from __future__ import annotations

import copy
import os
from pathlib import Path
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple, Union)

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset, LightGBMError
from .config import Config, resolve_params
from .utils.log import log_info, log_warning, scoped_verbosity
from .utils.timer import EnvCapture, Timer, timed


def _setup_metrics_endpoint(cfg: Config) -> None:
    """Start the per-process OpenMetrics /metrics endpoint
    (obs/export.py) when ``metrics_port`` is configured — via params
    or the LIGHTGBM_TPU_METRICS_PORT env var the fleet supervisors
    export. Each rank binds base + rank so a multi-process world's
    endpoints never collide; idempotent per process (cv folds and the
    pipeline's generations reuse the first server)."""
    # the env var OVERRIDES the param (config.py's documented
    # precedence): under a supervisor the exported base must win, or a
    # params-level metrics_port would collide with the supervisor's
    # own endpoint and desync the rank -> port attribution its
    # world-shape scraper relies on
    port = cfg.metrics_port
    env_port = os.environ.get("LIGHTGBM_TPU_METRICS_PORT")
    if env_port:
        try:
            port = int(env_port)
        except ValueError:
            pass
    if not port:
        return
    rank = 0
    rank_env = os.environ.get("LIGHTGBM_TPU_RANK")
    if rank_env:
        try:
            rank = int(rank_env)
        except ValueError:
            rank = 0
    else:
        try:
            import jax
            rank = jax.process_index()
        except Exception:
            rank = 0
    from .obs.export import ensure_metrics_server
    ensure_metrics_server(port + rank)


def _setup_telemetry(callbacks: List[Callable], model) -> None:
    """Activate run telemetry: honor ``LIGHTGBM_TPU_TELEMETRY=<path>``
    unless a telemetry callback is already present, then bind every
    recorder-bearing callback to the model before the first iteration
    (so iteration 0's event already carries tree stats)."""
    telem_path = os.environ.get("LIGHTGBM_TPU_TELEMETRY")
    if telem_path and not any(isinstance(cb, callback_mod._Telemetry)
                              for cb in callbacks):
        callbacks.append(callback_mod.telemetry(telem_path))
    for cb in callbacks:
        if isinstance(cb, callback_mod._Telemetry):
            cb.attach(model)


def _finish_callbacks(callbacks: List[Callable]) -> None:
    for cb in callbacks:
        if isinstance(cb, callback_mod._Telemetry):
            cb.finish()


# callbacks the fused scan window may legally run ahead of: they read
# no mid-window engine state the pop-per-update driver cannot serve
# per iteration (tree stats / phases / eval tuples — evaluation forces
# the eager path anyway, so these are inert on scan-eligible runs).
# Anything else (reset_parameter, user callbacks) pins the lookahead
# to 1: a window must never skate past a state read it cannot predict.
_SCAN_INERT_CALLBACKS = (callback_mod._Telemetry,
                         callback_mod._LogEvaluation,
                         callback_mod._RecordEvaluation,
                         callback_mod._EarlyStopping)


def _scan_lookahead(callbacks: List[Callable], iteration: int,
                    end_iteration: int,
                    engine_iteration: int,
                    eval_every: Optional[int] = None) -> int:
    """How many iterations the multi-iteration fused scan
    (models/gbdt.py, docs/FUSED.md) may run ahead of the callback loop
    starting at loop index ``iteration``: never past end-of-training,
    never past the next checkpoint firing — the Checkpoint callback
    keys on the engine's ABSOLUTE ``iter_`` (``engine_iteration``;
    offset from the loop index under init_model continued training),
    and `it % every_n == 0` reads the score, so windows must END on
    that cadence so snapshots see committed state — never past the
    loop's own inline evaluation (``eval_every`` = metric_freq when
    the train set is evaluated as a valid set; that cadence is
    loop-indexed), and 1 the moment an unknown callback could observe
    mid-window state."""
    from .resilience.checkpoint import Checkpoint

    horizon = end_iteration - iteration
    if eval_every is not None:
        every = max(1, int(eval_every))
        horizon = min(horizon, every - (iteration % every))
    for cb in callbacks:
        if isinstance(cb, Checkpoint):
            every = max(1, int(cb.every_n_iters))
            horizon = min(horizon, every - (engine_iteration % every))
        elif not isinstance(cb, _SCAN_INERT_CALLBACKS):
            return 1
    return max(1, horizon)

__all__ = ["train", "cv", "CVBooster"]


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          feval: Optional[Union[Callable, List[Callable]]] = None,
          init_model: Optional[Union[str, Booster]] = None,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None,
          fobj: Optional[Callable] = None,
          resume_from: Optional[str] = None) -> Booster:
    """Train one model (engine.py:109 analog).

    ``resume_from``: checkpoint directory written by the
    ``resilience.checkpoint`` callback — the newest valid snapshot is
    restored and training continues from its iteration toward
    ``num_boost_round`` *total* iterations (a directory without usable
    snapshots trains from scratch). With ``init_model``,
    ``num_boost_round`` counts the NEW iterations on top of the
    adopted trees (reference ``init_iteration + num_boost_round``
    semantics), and a snapshot written by such a run records the
    offset — so resuming with the *identical* command finishes at the
    same iteration the uninterrupted run would have. The
    ``LIGHTGBM_TPU_CHECKPOINT`` environment variable implies both
    ``resume_from`` and the checkpoint callback itself; see
    docs/RESILIENCE.md.
    """
    params = resolve_params(params)
    # num_boost_round from params wins (alias resolution)
    if "num_iterations" in params:
        num_boost_round = int(params["num_iterations"])
    params["num_iterations"] = num_boost_round
    cfg = Config.from_params(params)
    # host spans (utils/timer.py, docs/OBSERVABILITY.md "Tracing"):
    # train/job roots the call's trace; train/init is everything before
    # the first round (Booster and engine init: bins to the device,
    # packing, init score; callbacks and captures set up). Job-level,
    # so recorded with telemetry off: a clock pair and an append each.
    with scoped_verbosity(cfg.verbosity):
        job = None
        try:
            with timed("train/job", job=True, trace_root=True):
                with timed("train/init", job=True):
                    job = _train_setup(params, cfg, train_set,
                                       num_boost_round, valid_sets,
                                       valid_names, feval, init_model,
                                       callbacks, fobj, resume_from)
                return _train_rounds(params, cfg, fobj, feval, job)
        finally:
            # after train/job has closed, so that the recorder's last
            # drain carries the job's own span into its stream
            if job is not None:
                _finish_callbacks(job.callbacks)


class _TrainJob(NamedTuple):
    """What ``_train_setup`` hands the round loop."""
    booster: Booster
    callbacks: List[Callable]
    cbs_before: List[Callable]
    cbs_after: List[Callable]
    fault_plan: Any
    begin_iteration: int
    end_iteration: int
    evaluate: bool                   # any valid set, the train set included
    is_valid_contain_train: bool
    env_capture: Optional[EnvCapture]


def _train_setup(params: Dict[str, Any], cfg: Config, train_set: Dataset,
                 num_boost_round: int, valid_sets, valid_names, feval,
                 init_model, callbacks, fobj,
                 resume_from=None) -> _TrainJob:
    if cfg.objective == "custom" and fobj is None:
        raise LightGBMError(
            "objective=none requires a custom objective function (fobj)")

    if not isinstance(train_set, Dataset):
        raise TypeError("train() only accepts Dataset object(s)")

    _setup_metrics_endpoint(cfg)
    booster = Booster(params=params, train_set=train_set)

    # -- crash recovery (resilience/checkpoint.py): an explicit
    # resume_from wins; LIGHTGBM_TPU_CHECKPOINT is the hands-off env
    # switch that both resumes from and checkpoints into one directory
    from .resilience.checkpoint import (Checkpoint, checkpoint,
                                        load_latest_snapshot,
                                        restore_booster)
    ckpt_env = os.environ.get("LIGHTGBM_TPU_CHECKPOINT")
    resume_dir = resume_from or ckpt_env
    snap = load_latest_snapshot(resume_dir) if resume_dir else None
    resumed_iteration = 0
    if snap is not None:
        if init_model is not None:
            log_warning("resume_from checkpoint takes precedence over "
                        "init_model")
        resumed_iteration = restore_booster(booster, snap)
        log_info(f"Resumed from checkpoint {snap['path']} at iteration "
                 f"{resumed_iteration}")
    elif init_model is not None:
        # continued training (engine.py init_model -> num_init_iteration)
        if isinstance(init_model, (str, Path)):
            base = Booster(model_file=str(init_model))
        elif isinstance(init_model, Booster):
            base = init_model
        else:
            raise TypeError(
                "init_model should be a str, pathlib.Path or Booster")
        booster._preload(base)
    valid_sets = valid_sets or []
    is_valid_contain_train = False
    train_data_name = "training"
    name_list = []
    for i, vd in enumerate(valid_sets):
        if valid_names is not None and i < len(valid_names):
            name = valid_names[i]
        else:
            name = f"valid_{i}"
        if vd is train_set:
            is_valid_contain_train = True
            train_data_name = name
            booster._train_data_name = name
            continue
        vd.construct()
        booster.add_valid(vd, name)
        name_list.append(name)

    # callbacks setup (before/after split, ordering by .order)
    callbacks = list(callbacks) if callbacks else []
    if cfg.early_stopping_round and cfg.early_stopping_round > 0:
        callbacks.append(callback_mod.early_stopping(
            cfg.early_stopping_round,
            first_metric_only=cfg.first_metric_only,
            min_delta=cfg.early_stopping_min_delta,
            verbose=cfg.verbosity >= 1))
    if cfg.verbosity >= 1 and cfg.is_provide_training_metric:
        pass  # training metric printed through evaluation list below
    if ckpt_env and not any(isinstance(cb, Checkpoint)
                            for cb in callbacks):
        every_raw = os.environ.get("LIGHTGBM_TPU_CHECKPOINT_EVERY", "1")
        try:
            every = max(1, int(every_raw or 1))
        except ValueError:
            log_warning("LIGHTGBM_TPU_CHECKPOINT_EVERY="
                        f"{every_raw!r} is not an integer; "
                        "checkpointing every iteration")
            every = 1
        callbacks.append(checkpoint(ckpt_env, every_n_iters=every))
    _setup_telemetry(callbacks, booster)
    # lists, not a set (tpulint TPL005): `sorted` is stable, so
    # callbacks with EQUAL .order used to run in set hash order —
    # varying per process (PYTHONHASHSEED) and across SPMD ranks.
    # Registration order now breaks ties, like the cv() path.
    cbs_before = [cb for cb in callbacks
                  if getattr(cb, "before_iteration", False)]
    cbs_after = [cb for cb in callbacks
                 if not getattr(cb, "before_iteration", False)]
    cbs_before = sorted(cbs_before, key=lambda c: getattr(c, "order", 0))
    cbs_after = sorted(cbs_after, key=lambda c: getattr(c, "order", 0))

    from .resilience import watchdog
    from .resilience.faults import FaultPlan
    fault_plan = FaultPlan.from_env()
    # host-collective deadline for this run's sync points
    # (telemetry/checkpoint collectives; parallel/spmd.py). Env var
    # still overrides inside deadline_seconds().
    watchdog.configure(cfg.collective_timeout_sec)

    # iteration window (reference engine.py: range(init_iteration,
    # init_iteration + num_boost_round)): continued training
    # (init_model) adds num_boost_round NEW iterations on top of the
    # adopted trees, with loop indices running on the ENGINE-ABSOLUTE
    # iteration so callbacks/eval cadence and checkpoints agree with
    # the engine's own iter_. Resume continues toward the SAME end the
    # uninterrupted run had (train 20 == train 10 then resume to 20;
    # the snapshot records the init offset, so a crashed warm-start
    # retrain — the pipeline's rank_kill chaos, docs/PIPELINE.md —
    # relaunched with the identical command still finishes at
    # init + num_boost_round instead of stopping short).
    init_iteration = 0
    if booster._engine is not None:
        init_iteration = int(getattr(booster._engine,
                                     "init_iteration", 0))
    begin_iteration = resumed_iteration if snap is not None \
        else init_iteration
    end_iteration = max(begin_iteration,
                        init_iteration + num_boost_round)
    # env-driven device captures (LIGHTGBM_TPU_TRACE_TO whole-run /
    # LIGHTGBM_TPU_XPROF=dir:iters=A-B window); None — and zero
    # per-iteration cost — when neither knob is set
    return _TrainJob(booster, callbacks, cbs_before, cbs_after,
                     fault_plan, begin_iteration, end_iteration,
                     bool(valid_sets or is_valid_contain_train),
                     is_valid_contain_train, EnvCapture.from_env())


def _train_rounds(params: Dict[str, Any], cfg: Config, fobj, feval,
                  job: _TrainJob) -> Booster:
    (booster, callbacks, cbs_before, cbs_after, fault_plan,
     begin_iteration, end_iteration, evaluate, is_valid_contain_train,
     env_capture) = job
    evaluation_result_list: List[Tuple] = []
    try:
        for i in range(begin_iteration, end_iteration):
            fault_plan.maybe_kill(i)
            fault_plan.maybe_distributed_fault(i)
            if env_capture is not None:
                env_capture.before_iteration(i)
            # per-round spans: one flag check each with nothing live
            with timed("train/round"):
                if booster._engine is not None:
                    # fused-scan lookahead (docs/FUSED.md): the engine
                    # loop is the only place that knows the callback
                    # set and end_iteration, so it bounds how far one
                    # scan window may run ahead of the per-iteration
                    # cadence. valid_sets=[train_set] keeps
                    # engine.valid_sets empty (scan stays eligible) but
                    # this loop then evaluates the TRAIN score inline
                    # every metric_freq iterations — windows must end
                    # on that cadence too.
                    booster._engine._scan_horizon = _scan_lookahead(
                        callbacks, i, end_iteration,
                        engine_iteration=int(booster._engine.iter_),
                        eval_every=(max(1, cfg.metric_freq)
                                    if is_valid_contain_train else None))
                if cbs_before:
                    with timed("callbacks/before"):
                        for cb in cbs_before:
                            cb(callback_mod.CallbackEnv(
                                model=booster, params=params, iteration=i,
                                begin_iteration=begin_iteration,
                                end_iteration=end_iteration,
                                evaluation_result_list=None))
                with timed("train/update"):
                    finished = booster.update(fobj=fobj)

                evaluation_result_list = []
                if evaluate and ((i + 1) % max(1, cfg.metric_freq) == 0
                                 or i == end_iteration - 1):
                    with timed("engine/eval"):
                        if is_valid_contain_train:
                            evaluation_result_list.extend(
                                booster.eval_train(feval))
                        evaluation_result_list.extend(
                            booster.eval_valid(feval))
                try:
                    if cbs_after:
                        with timed("callbacks/after"):
                            for cb in cbs_after:
                                cb(callback_mod.CallbackEnv(
                                    model=booster, params=params,
                                    iteration=i,
                                    begin_iteration=begin_iteration,
                                    end_iteration=end_iteration,
                                    evaluation_result_list=(
                                        evaluation_result_list)))
                except callback_mod.EarlyStopException as es:
                    booster.best_iteration = es.best_iteration + 1
                    evaluation_result_list = es.best_score
                    # roll the model back to best_iteration for
                    # storage parity
                    break
            if env_capture is not None:
                env_capture.after_iteration(i)
            if finished:
                log_info("Stopped training because there are no more "
                         "leaves that meet the split requirements")
                break
        # guard flags of the last fused iteration are still in flight
        # (the async check runs one iteration late): drain them now so
        # a fault on the final iteration still enforces its policy
        if booster._engine is not None:
            booster._engine.finish_faults()
    finally:
        if booster._engine is not None:
            # restore the documented direct-API behavior: only this
            # loop may grant lookahead, so a booster returned with a
            # stale multi-iteration horizon (break on stall / early
            # stop / an exception) must not dispatch windows from
            # plain update() calls
            booster._engine._scan_horizon = 1
        if env_capture is not None:
            # finalize capture files even when the loop raised
            env_capture.close()

    if booster.best_iteration <= 0:
        booster.best_iteration = booster.current_iteration()
    for item in (evaluation_result_list or []):
        booster.best_score.setdefault(item[0], {})[item[1]] = item[2]
    if Timer.enabled():
        Timer.log_summary()
    return booster


class CVBooster:
    """Ensemble of per-fold boosters (engine.py:354)."""

    def __init__(self, model_file: Optional[str] = None):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def _append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name: str):
        def handler_function(*args: Any, **kwargs: Any) -> List[Any]:
            return [getattr(b, name)(*args, **kwargs)
                    for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int, params: Dict,
                  seed: int, stratified: bool, shuffle: bool):
    full_data.construct()
    num_data = full_data.num_data()
    label = np.asarray(full_data.get_label())
    if folds is not None:
        if not hasattr(folds, "__iter__") and not hasattr(folds, "split"):
            raise AttributeError(
                "folds should be a generator or iterator of (train_idx, "
                "test_idx) tuples or scikit-learn splitter object")
        if hasattr(folds, "split"):
            group = full_data.get_group()
            group_info = None if group is None else np.asarray(group)
            flatted_group = np.zeros(num_data, dtype=np.int64)
            if group_info is not None:
                flatted_group = np.repeat(range(len(group_info)), group_info)
            folds = folds.split(X=np.empty(num_data), y=label,
                                groups=flatted_group)
        return list(folds)
    rng = np.random.RandomState(seed)
    if full_data.get_group() is not None:
        # group-aware folds: whole queries per fold
        group = np.asarray(full_data.get_group())
        nq = len(group)
        q_idx = np.arange(nq)
        if shuffle:
            rng.shuffle(q_idx)
        q_fold = np.arange(nq) % nfold
        row_fold = np.zeros(num_data, np.int64)
        starts = np.concatenate([[0], np.cumsum(group)])
        for qi, f in zip(q_idx, q_fold):
            row_fold[starts[qi]:starts[qi + 1]] = f
        return [(np.where(row_fold != f)[0], np.where(row_fold == f)[0])
                for f in range(nfold)]
    if stratified:
        # label-sorted striping keeps class ratios per fold; with shuffle,
        # rows are permuted within each label block first so fold
        # membership is random rather than row-order-determined
        order = np.argsort(label, kind="stable")
        if shuffle:
            sorted_labels = label[order]
            block_starts = np.concatenate(
                [[0], np.where(np.diff(sorted_labels) != 0)[0] + 1,
                 [num_data]])
            for a, b in zip(block_starts[:-1], block_starts[1:]):
                perm = rng.permutation(b - a)
                order[a:b] = order[a:b][perm]
        fold_of = np.empty(num_data, np.int64)
        fold_of[order] = np.arange(num_data) % nfold
        return [(np.where(fold_of != f)[0], np.where(fold_of == f)[0])
                for f in range(nfold)]
    idx = np.arange(num_data)
    if shuffle:
        rng.shuffle(idx)
    return [(np.concatenate([idx[: (f * num_data) // nfold],
                             idx[((f + 1) * num_data) // nfold:]]),
             idx[(f * num_data) // nfold: ((f + 1) * num_data) // nfold])
            for f in range(nfold)]


def _agg_cv_result(raw_results: List[List[Tuple]]):
    cvmap: Dict[str, List[float]] = {}
    metric_type: Dict[str, bool] = {}
    for one_result in raw_results:
        for one_line in one_result:
            key = f"{one_line[0]} {one_line[1]}"
            metric_type[key] = one_line[3]
            cvmap.setdefault(key, [])
            cvmap[key].append(one_line[2])
    return [("cv_agg", k, float(np.mean(v)), metric_type[k],
             float(np.std(v))) for k, v in cvmap.items()]


def cv(params: Dict[str, Any], train_set: Dataset,
       num_boost_round: int = 100, folds=None, nfold: int = 5,
       stratified: bool = True, shuffle: bool = True,
       metrics=None, feval=None, init_model=None,
       fpreproc=None, seed: int = 0, callbacks=None,
       eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, Any]:
    """K-fold cross validation (engine.py:625 analog)."""
    if not isinstance(train_set, Dataset):
        raise TypeError("cv() only accepts Dataset object(s)")
    params = resolve_params(params)
    if "num_iterations" in params:
        num_boost_round = int(params["num_iterations"])
    if metrics is not None:
        params["metric"] = metrics
    cfg = Config.from_params(params)
    with scoped_verbosity(cfg.verbosity):
        return _cv_impl(params, cfg, train_set, num_boost_round, folds,
                        nfold, stratified, shuffle, feval, fpreproc, seed,
                        callbacks, eval_train_metric, return_cvbooster)


def _cv_impl(params: Dict[str, Any], cfg: Config, train_set: Dataset,
             num_boost_round: int, folds, nfold, stratified, shuffle,
             feval, fpreproc, seed, callbacks, eval_train_metric,
             return_cvbooster) -> Dict[str, Any]:
    if cfg.objective in ("binary", "multiclass", "multiclassova",
                         "lambdarank", "rank_xendcg"):
        stratified = stratified and cfg.objective == "binary"
    else:
        stratified = False

    train_set.construct()
    folds = _make_n_folds(train_set, folds, nfold, params, seed,
                          stratified, shuffle)
    label = np.asarray(train_set.get_label())
    weight = train_set.get_weight()
    group = train_set.get_group()
    # raw feature matrix must still be around for fold slicing
    X = train_set.host_bins()  # binned is fine: folds share bin mappers

    cvbooster = CVBooster()
    results: Dict[str, List[float]] = {}

    boosters = []
    for train_idx, test_idx in folds:
        tr = _subset_dataset(train_set, train_idx, params)
        te = _subset_dataset(train_set, test_idx, params)
        if fpreproc is not None:
            tr, te, params = fpreproc(tr, te, params.copy())
        booster = Booster(params=params, train_set=tr)
        booster.add_valid(te, "valid")
        if eval_train_metric:
            booster._train_data_name = "train"
        boosters.append(booster)
        cvbooster._append(booster)

    callbacks = list(callbacks) if callbacks else []
    if cfg.early_stopping_round and cfg.early_stopping_round > 0:
        callbacks.append(callback_mod.early_stopping(
            cfg.early_stopping_round,
            first_metric_only=cfg.first_metric_only,
            verbose=cfg.verbosity >= 1))
    _setup_telemetry(callbacks, cvbooster)
    cbs_before = sorted((cb for cb in callbacks
                         if getattr(cb, "before_iteration", False)),
                        key=lambda c: getattr(c, "order", 0))
    cbs_after = sorted((cb for cb in callbacks
                        if not getattr(cb, "before_iteration", False)),
                       key=lambda c: getattr(c, "order", 0))

    try:
        for i in range(num_boost_round):
            for cb in cbs_before:
                cb(callback_mod.CallbackEnv(
                    model=cvbooster, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=None))
            for booster in boosters:
                booster.update()
            raw = []
            with timed("engine/eval"):
                for booster in boosters:
                    one = []
                    if eval_train_metric:
                        one.extend(booster.eval_train(feval))
                    one.extend(booster.eval_valid(feval))
                    raw.append(one)
            res = _agg_cv_result(raw)
            for (_, key, mean, _, std) in res:
                results.setdefault(f"{key}-mean", []).append(mean)
                results.setdefault(f"{key}-stdv", []).append(std)
            try:
                for cb in cbs_after:
                    cb(callback_mod.CallbackEnv(
                        model=cvbooster, params=params, iteration=i,
                        begin_iteration=0, end_iteration=num_boost_round,
                        evaluation_result_list=res))
            except callback_mod.EarlyStopException as es:
                cvbooster.best_iteration = es.best_iteration + 1
                for bst in boosters:
                    bst.best_iteration = cvbooster.best_iteration
                for k in results:
                    results[k] = results[k][: cvbooster.best_iteration]
                break
    finally:
        _finish_callbacks(callbacks)

    if return_cvbooster:
        results["cvbooster"] = cvbooster
    return dict(results)


def _subset_dataset(full: Dataset, idx: np.ndarray,
                    params: Dict) -> Dataset:
    """Row-subset sharing the parent's bin mappers (Dataset::CopySubrow /
    Subset analog, dataset.h:661)."""
    full.construct()
    sub = Dataset.__new__(Dataset)
    sub.__dict__.update({k: v for k, v in full.__dict__.items()})
    sub.reference = full
    sub._bins = full._bins[idx]
    sub._device_bins = None
    sub._n = len(idx)
    rn = full.raw_numeric()
    sub._raw_numeric = None if rn is None else rn[idx]
    sub._device_raw = None
    sub.label = np.asarray(full.get_label())[idx]
    w = full.get_weight()
    sub.weight = None if w is None else np.asarray(w)[idx]
    init = full.get_init_score()
    sub.init_score = None if init is None else np.asarray(init)[idx]
    pos = full.get_position()
    sub.position = None if pos is None else np.asarray(pos)[idx]
    qb = full.query_boundaries()
    if qb is not None:
        # reconstruct boundaries for the kept (whole) queries
        row_query = np.searchsorted(qb, idx, side="right") - 1
        kept_q, counts = np.unique(row_query, return_counts=True)
        sub._query_boundaries = np.concatenate(
            [[0], np.cumsum(counts)]).astype(np.int64)
    sub.used_indices = np.asarray(idx)
    sub._handle = True
    return sub
