"""Parameter/config system for the TPU-native GBDT framework.

Mirrors the semantics of the reference's annotated ``struct Config``
(/root/reference/include/LightGBM/config.h, src/io/config.cpp): a single flat
parameter namespace with ~150 aliases, bounds checks, and a canonical string
form — re-designed as a Python dataclass that is the single source of truth
for parameter names, aliases, defaults and constraints.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple, Union

__all__ = ["Config", "ALIASES", "resolve_params", "choose_param_value"]


# ---------------------------------------------------------------------------
# Alias table: alias -> canonical name.
# Mirrors the alias map generated into config_auto.cpp in the reference
# (and _ConfigAliases in python-package/lightgbm/basic.py).
# ---------------------------------------------------------------------------
ALIASES: Dict[str, str] = {
    # core
    "config_file": "config",
    "task_type": "task",
    "objective_type": "objective",
    "app": "objective",
    "application": "objective",
    "loss": "objective",
    "boosting_type": "boosting",
    "boost": "boosting",
    "train": "data",
    "train_data": "data",
    "train_data_file": "data",
    "data_filename": "data",
    "test": "valid",
    "valid_data": "valid",
    "valid_data_file": "valid",
    "test_data": "valid",
    "test_data_file": "valid",
    "valid_filenames": "valid",
    "num_trees": "num_iterations",
    "num_iteration": "num_iterations",
    "num_tree": "num_iterations",
    "num_round": "num_iterations",
    "num_rounds": "num_iterations",
    "nrounds": "num_iterations",
    "num_boost_round": "num_iterations",
    "n_iter": "num_iterations",
    "n_estimators": "num_iterations",
    "max_iter": "num_iterations",
    "shrinkage_rate": "learning_rate",
    "eta": "learning_rate",
    "num_leaf": "num_leaves",
    "max_leaves": "num_leaves",
    "max_leaf": "num_leaves",
    "max_leaf_nodes": "num_leaves",
    "tree": "tree_learner",
    "tree_type": "tree_learner",
    "tree_learner_type": "tree_learner",
    "num_thread": "num_threads",
    "nthread": "num_threads",
    "nthreads": "num_threads",
    "n_jobs": "num_threads",
    "device": "device_type",
    "random_seed": "seed",
    "random_state": "seed",
    # learning control
    "min_data_per_leaf": "min_data_in_leaf",
    "min_data": "min_data_in_leaf",
    "min_child_samples": "min_data_in_leaf",
    "min_samples_leaf": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf",
    "min_hessian": "min_sum_hessian_in_leaf",
    "min_child_weight": "min_sum_hessian_in_leaf",
    "sub_row": "bagging_fraction",
    "subsample": "bagging_fraction",
    "bagging": "bagging_fraction",
    "pos_sub_row": "pos_bagging_fraction",
    "pos_subsample": "pos_bagging_fraction",
    "pos_bagging": "pos_bagging_fraction",
    "neg_sub_row": "neg_bagging_fraction",
    "neg_subsample": "neg_bagging_fraction",
    "neg_bagging": "neg_bagging_fraction",
    "subsample_freq": "bagging_freq",
    "bagging_fraction_seed": "bagging_seed",
    "sub_feature": "feature_fraction",
    "colsample_bytree": "feature_fraction",
    "sub_feature_bynode": "feature_fraction_bynode",
    "colsample_bynode": "feature_fraction_bynode",
    "extra_tree": "extra_trees",
    "early_stopping_rounds": "early_stopping_round",
    "early_stopping": "early_stopping_round",
    "n_iter_no_change": "early_stopping_round",
    "max_tree_output": "max_delta_step",
    "max_leaf_output": "max_delta_step",
    "reg_alpha": "lambda_l1",
    "l1_regularization": "lambda_l1",
    "reg_lambda": "lambda_l2",
    "lambda": "lambda_l2",
    "l2_regularization": "lambda_l2",
    "min_split_gain": "min_gain_to_split",
    "rate_drop": "drop_rate",
    "topk": "top_k",
    "mc": "monotone_constraints",
    "monotone_constraint": "monotone_constraints",
    "monotonic_cst": "monotone_constraints",
    "monotone_constraining_method": "monotone_constraints_method",
    "mc_method": "monotone_constraints_method",
    "monotone_splits_penalty": "monotone_penalty",
    "ms_penalty": "monotone_penalty",
    "mc_penalty": "monotone_penalty",
    "feature_contrib": "feature_contri",
    "fc": "feature_contri",
    "fp": "feature_contri",
    "feature_penalty": "feature_contri",
    "fs": "forcedsplits_filename",
    "forced_splits_filename": "forcedsplits_filename",
    "forced_splits_file": "forcedsplits_filename",
    "forced_splits": "forcedsplits_filename",
    "verbose": "verbosity",
    # dataset
    "linear_trees": "linear_tree",
    "max_bins": "max_bin",
    "subsample_for_bin": "bin_construct_sample_cnt",
    "data_seed": "data_random_seed",
    "is_sparse": "is_enable_sparse",
    "enable_sparse": "is_enable_sparse",
    "sparse": "is_enable_sparse",
    "is_enable_bundle": "enable_bundle",
    "bundle": "enable_bundle",
    "is_pre_partition": "pre_partition",
    "two_round_loading": "two_round",
    "use_two_round_loading": "two_round",
    "has_header": "header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column",
    "group_id": "group_column",
    "query_column": "group_column",
    "query": "group_column",
    "query_id": "group_column",
    "ignore_feature": "ignore_column",
    "blacklist": "ignore_column",
    "cat_feature": "categorical_feature",
    "categorical_column": "categorical_feature",
    "cat_column": "categorical_feature",
    "is_save_binary": "save_binary",
    "is_save_binary_file": "save_binary",
    # predict
    "is_predict_raw_score": "predict_raw_score",
    "predict_rawscore": "predict_raw_score",
    "raw_score": "predict_raw_score",
    "is_predict_leaf_index": "predict_leaf_index",
    "leaf_index": "predict_leaf_index",
    "is_predict_contrib": "predict_contrib",
    "contrib": "predict_contrib",
    # objective
    "num_classes": "num_class",
    "unbalance": "is_unbalance",
    "unbalanced_sets": "is_unbalance",
    "objective_seed": "seed",
    "ndcg_eval_at": "eval_at",
    "ndcg_at": "eval_at",
    "map_eval_at": "eval_at",
    "map_at": "eval_at",
    # metric
    "metrics": "metric",
    "metric_types": "metric",
    "output_freq": "metric_freq",
    "training_metric": "is_provide_training_metric",
    "is_training_metric": "is_provide_training_metric",
    "train_metric": "is_provide_training_metric",
    # network
    "num_machine": "num_machines",
    "local_port": "local_listen_port",
    "port": "local_listen_port",
    "machine_list_filename": "machine_list_file",
    "machine_list": "machine_list_file",
    "mlist": "machine_list_file",
    "workers": "machines",
    "nodes": "machines",
    # io
    "model_output": "output_model",
    "model_out": "output_model",
    "model_input": "input_model",
    "model_in": "input_model",
    "predict_result": "output_result",
    "prediction_result": "output_result",
    "predict_name": "output_result",
    "prediction_name": "output_result",
    "pred_name": "output_result",
    "name_pred": "output_result",
    "init_score_filename": "initscore_filename",
    "init_score_file": "initscore_filename",
    "init_score": "initscore_filename",
    "input_init_score": "initscore_filename",
}

_OBJECTIVE_ALIASES = {
    "regression": "regression",
    "regression_l2": "regression",
    "l2": "regression",
    "mean_squared_error": "regression",
    "mse": "regression",
    "l2_root": "regression",
    "root_mean_squared_error": "regression",
    "rmse": "regression",
    "regression_l1": "regression_l1",
    "l1": "regression_l1",
    "mean_absolute_error": "regression_l1",
    "mae": "regression_l1",
    "huber": "huber",
    "fair": "fair",
    "poisson": "poisson",
    "quantile": "quantile",
    "mape": "mape",
    "mean_absolute_percentage_error": "mape",
    "gamma": "gamma",
    "tweedie": "tweedie",
    "binary": "binary",
    "multiclass": "multiclass",
    "softmax": "multiclass",
    "multiclassova": "multiclassova",
    "multiclass_ova": "multiclassova",
    "ova": "multiclassova",
    "ovr": "multiclassova",
    "cross_entropy": "cross_entropy",
    "xentropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda",
    "xentlambda": "cross_entropy_lambda",
    "lambdarank": "lambdarank",
    "rank_xendcg": "rank_xendcg",
    "xendcg": "rank_xendcg",
    "xe_ndcg": "rank_xendcg",
    "xe_ndcg_mart": "rank_xendcg",
    "xendcg_mart": "rank_xendcg",
    "none": "custom",
    "null": "custom",
    "custom": "custom",
    "na": "custom",
}


def canonical_objective(name: str) -> str:
    key = name.strip().lower()
    if key not in _OBJECTIVE_ALIASES:
        raise ValueError(f"Unknown objective: {name}")
    return _OBJECTIVE_ALIASES[key]


def choose_param_value(main_param_name: str, params: Dict[str, Any],
                       default_value: Any = None) -> Dict[str, Any]:
    """Resolve aliases for one parameter in-place-ish (returns a copy).

    Mirrors ``_choose_param_value`` (reference python-package basic.py:612).
    Precedence: the canonical name wins; otherwise first alias found.
    """
    params = dict(params)
    if main_param_name in params:
        pass
    else:
        for alias, main in ALIASES.items():
            if main == main_param_name and alias in params:
                params[main_param_name] = params.pop(alias)
                break
        else:
            if default_value is not None:
                params[main_param_name] = default_value
    # drop remaining aliases for this param
    for alias, main in list(ALIASES.items()):
        if main == main_param_name and alias in params:
            params.pop(alias)
    return params


def resolve_params(params: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Map every aliased key to its canonical name. Canonical keys win."""
    out: Dict[str, Any] = {}
    if not params:
        return out
    aliased: Dict[str, Any] = {}
    for k, v in params.items():
        canon = ALIASES.get(k, k)
        if canon == k:
            out[k] = v
        else:
            aliased.setdefault(canon, v)
    for k, v in aliased.items():
        out.setdefault(k, v)
    return out


def _parse_list(v: Any, typ) -> list:
    if v is None:
        return []
    if isinstance(v, str):
        v = v.replace(";", ",")
        return [typ(x) for x in v.split(",") if x.strip() != ""]
    if isinstance(v, (list, tuple)):
        return [typ(x) for x in v]
    return [typ(v)]


_TRUE = {"true", "1", "yes", "on", "+", "t", "y"}
_FALSE = {"false", "0", "no", "off", "-", "f", "n"}


def _parse_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    if isinstance(v, (int, float)):
        return bool(v)
    s = str(v).strip().lower()
    if s in _TRUE:
        return True
    if s in _FALSE:
        return False
    raise ValueError(f"Cannot parse boolean from {v!r}")


@dataclass
class Config:
    """Canonical training configuration.

    Field set mirrors the reference's ``Config`` struct (config.h:39-1322);
    bounds (``check`` annotations in the reference) are enforced in
    ``__post_init__``.
    """

    # ---- core ----
    task: str = "train"
    objective: str = "regression"
    boosting: str = "gbdt"
    data_sample_strategy: str = "bagging"  # bagging | goss
    data: str = ""
    valid: List[str] = field(default_factory=list)
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    # serial | feature | data | voting | auto. "auto" replaces the
    # static flag with the payload-model decision (parallel/comms.py
    # choose_parallel_mode): feature-parallel for replicable data,
    # data-parallel while one histogram reduction stays cheap at the
    # chosen hist_comm wire dtype, voting beyond (the reference's
    # Parallel-Learning-Guide table, measured instead of adjectival).
    tree_learner: str = "serial"
    num_threads: int = 0
    device_type: str = "tpu"  # cpu | tpu
    seed: Optional[int] = None
    deterministic: bool = False

    # ---- learning control ----
    force_col_wise: bool = False
    force_row_wise: bool = False
    histogram_pool_size: float = -1.0
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    bagging_fraction: float = 1.0
    pos_bagging_fraction: float = 1.0
    neg_bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    bagging_by_query: bool = False
    feature_fraction: float = 1.0
    feature_fraction_bynode: float = 1.0
    feature_fraction_seed: int = 2
    extra_trees: bool = False
    extra_seed: int = 6
    early_stopping_round: int = 0
    early_stopping_min_delta: float = 0.0
    first_metric_only: bool = False
    max_delta_step: float = 0.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    linear_lambda: float = 0.0
    min_gain_to_split: float = 0.0
    drop_rate: float = 0.1  # dart
    max_drop: int = 50  # dart
    skip_drop: float = 0.5  # dart
    xgboost_dart_mode: bool = False
    uniform_drop: bool = False
    drop_seed: int = 4
    top_rate: float = 0.2  # goss
    other_rate: float = 0.1  # goss
    min_data_per_group: int = 100
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    top_k: int = 20  # voting parallel
    monotone_constraints: List[int] = field(default_factory=list)
    monotone_constraints_method: str = "basic"
    monotone_penalty: float = 0.0
    feature_contri: List[float] = field(default_factory=list)
    forcedsplits_filename: str = ""
    refit_decay_rate: float = 0.9
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    cegb_penalty_feature_lazy: List[float] = field(default_factory=list)
    cegb_penalty_feature_coupled: List[float] = field(default_factory=list)
    path_smooth: float = 0.0
    interaction_constraints: Any = ""
    verbosity: int = 1
    input_model: str = ""
    output_model: str = "LightGBM_model.txt"
    saved_feature_importance_type: int = 0
    snapshot_freq: int = -1
    use_quantized_grad: bool = False
    num_grad_quant_bins: int = 4
    quant_train_renew_leaf: bool = False
    stochastic_rounding: bool = True
    # non-finite guard on gradients/hessians/fitted leaf values, fused
    # into the jitted boosting step (resilience/): "raise" fails fast
    # with a LightGBMError, "skip_tree" drops the poisoned iteration's
    # trees (they become no-op constants) and keeps training, "clamp"
    # replaces NaN/Inf with finite values and keeps the trees
    nonfinite_policy: str = "raise"
    # multi-iteration fused scan (docs/FUSED.md): trace N boosting
    # iterations into ONE lax.scan program with donated score/bagging
    # carries and a window-batched tree-pack fetch, deleting the
    # per-iteration dispatch + host round-trip from the hot loop.
    # "auto" (default) stays per-iteration until the Higgs-shaped
    # fused_iter_bench scan arm measures a win on chip
    # (LIGHTGBM_TPU_AUTO_SCAN_ITERS=N opts auto in for measurement;
    # LIGHTGBM_TPU_DISABLE_SCAN=1 is the kill switch). An explicit
    # integer N>1 enables windows of up to N iterations; the engine
    # shrinks windows to the next checkpoint/end-of-training boundary
    # and falls back to the per-iteration fused path for configs the
    # scan cannot carry (feature_fraction host RNG, GOSS/DART, valid
    # sets — see GBDTBooster._scan_ok)
    fused_scan_iters: Any = "auto"

    # ---- dataset ----
    linear_tree: bool = False
    max_bin: int = 255
    max_bin_by_feature: List[int] = field(default_factory=list)
    min_data_in_bin: int = 3
    bin_construct_sample_cnt: int = 200000
    data_random_seed: int = 1
    is_enable_sparse: bool = True
    enable_bundle: bool = True
    use_missing: bool = True
    zero_as_missing: bool = False
    feature_pre_filter: bool = True
    pre_partition: bool = False
    two_round: bool = False
    # out-of-core streaming ingestion (lightgbm_tpu/data/, docs/DATA.md):
    # rows per ingest chunk for the two-pass construct. 0 (default)
    # keeps in-memory inputs eager; chunked sources (RowChunkSource /
    # Sequence / generator factories) always stream and use this as
    # their chunk size when set. > 0 additionally streams CSV/TSV and
    # parquet paths chunk-by-chunk, so the dense float matrix never
    # exists and peak host memory scales with ingest_chunk_rows x
    # n_features (plus the bin_construct_sample_cnt sample), not with
    # dataset rows
    ingest_chunk_rows: int = 0
    header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""
    categorical_feature: Any = ""
    forcedbins_filename: str = ""
    save_binary: bool = False
    precise_float_parser: bool = False
    parser_config_file: str = ""

    # ---- predict ----
    start_iteration_predict: int = 0
    num_iteration_predict: int = -1
    predict_raw_score: bool = False
    predict_leaf_index: bool = False
    predict_contrib: bool = False
    predict_disable_shape_check: bool = False
    pred_early_stop: bool = False
    pred_early_stop_freq: int = 10
    pred_early_stop_margin: float = 10.0
    output_result: str = "LightGBM_predict_result.txt"

    # ---- serve ----
    # production inference daemon (lightgbm_tpu/serve/,
    # docs/SERVING.md): micro-batching window in milliseconds — how
    # long the batcher waits for more requests before dispatching a
    # partial batch (0 = dispatch immediately)
    serve_batch_window_ms: float = 2.0
    # largest device batch (power of two); bigger requests are split,
    # smaller ones pad up to their power-of-two bucket so arbitrary
    # request sizes never recompile the predict program
    serve_max_batch_rows: int = 16384
    # smallest row bucket (power of two): requests below it pad to it,
    # bounding the jit cache at log2(max/min)+1 entries per model
    serve_min_bucket_rows: int = 16
    # pending-row budget: a submit that would exceed it is rejected
    # (backpressure) instead of growing an unbounded queue
    serve_queue_rows: int = 131072
    # seconds between {"event": "serve"} telemetry lines
    serve_stats_interval_sec: float = 10.0
    # seconds between polls of the hot-swap watch directory
    serve_watch_interval_sec: float = 1.0
    # load shedding (docs/SERVING.md "Overload policy"): soft backlog
    # threshold in pending rows — above it the batcher worker sheds
    # its OLDEST queued requests with a typed {"shed": true} reply
    # until the backlog is back under the threshold, so fresh arrivals
    # keep bounded latency instead of every caller timing out
    # together. 0 (default) disables; must stay below serve_queue_rows
    # (the hard admission wall) to ever fire
    serve_shed_queue_rows: int = 0
    # per-request latency budget in milliseconds: a queued request
    # that already waited longer is shed at dequeue time (its deadline
    # is blown; serving it would only steal capacity from requests
    # that can still meet theirs). 0 (default) disables
    serve_shed_p99_ms: float = 0.0
    # graceful-shutdown deadline in seconds: on SIGTERM or the
    # protocol `shutdown` command the daemon stops accepting, drains
    # already-accepted requests for up to this long, waits for the
    # replies to reach the wire, and only then closes the socket — a
    # supervised restart never drops an accepted request
    serve_shutdown_grace_sec: float = 15.0
    # replica autoscaling floor (resilience/autoscale.py,
    # docs/RESILIENCE.md "Autoscaling policy"): the fleet supervisor
    # never retires below this many replicas
    serve_min_replicas: int = 1
    # autoscaling ceiling: the fleet supervisor spawns replicas up to
    # this count on load (fleet QPS / p99 / shed signals) and retires
    # them — graceful drain, zero dropped in-flight requests — when
    # the load subsides. 0 (default) disables autoscaling (fixed
    # fleet)
    serve_max_replicas: int = 0
    # scale-up QPS threshold: scale up when the fleet-total QPS
    # exceeds this per active replica (0 disables the QPS signal)
    autoscale_up_qps: float = 0.0
    # scale-down QPS threshold: scale down only when the fleet-total
    # QPS would still stay under this per replica with one replica
    # FEWER. Keep it strictly below autoscale_up_qps — that gap is
    # the hysteresis band that stops the fleet flapping (0 disables
    # scale-down)
    autoscale_down_qps: float = 0.0
    # scale-up latency threshold: scale up when any replica's p99
    # exceeds this many milliseconds (0 disables the latency signal)
    autoscale_up_p99_ms: float = 0.0
    # cooldown seconds after ANY scaling action before the next
    # scale-up / scale-down may fire (the other half of hysteresis:
    # one load spike cannot double-scale between scrapes)
    autoscale_up_cooldown_sec: float = 5.0
    autoscale_down_cooldown_sec: float = 15.0

    # ---- observability (lightgbm_tpu/obs/; docs/OBSERVABILITY.md) ----
    # base port of the OpenMetrics /metrics HTTP endpoint
    # (obs/export.py): every process of a fleet exports its
    # MetricsRegistry at metrics_port + its rank (trainer ranks under
    # `launch`, serve replicas via `serve --metrics-port`, the
    # supervisors at the base port). 0 (default) disables the
    # endpoint; the LIGHTGBM_TPU_METRICS_PORT env var (exported by
    # the supervisors) overrides
    metrics_port: int = 0
    # seconds between fleet metric scrapes: the cadence at which the
    # `launch` fleet supervisor and the `pipeline` driver poll their
    # children's stats into {"event": "fleet"} telemetry records
    # (docs/OBSERVABILITY.md "Fleet events"). 0 disables scraping
    metrics_scrape_interval_sec: float = 5.0
    # distributed-tracing sample rate (obs/trace.py, docs/
    # OBSERVABILITY.md "Tracing"): the pipeline's load generator
    # originates a trace on every Nth request — the traced request
    # carries a {"trace": ...} protocol field and the serve replica
    # answers it with queue-wait / batch-window / dispatch / reply
    # spans, merged by `python -m lightgbm_tpu trace <dir>`.
    # 0 disables request-trace sampling (train/publish/swap spans are
    # always on — they cost one clock pair per iteration/publication)
    trace_sample_every: int = 16

    # ---- publish (resilience/publisher.py; docs/PIPELINE.md) ----
    # retry budget for one atomic model publication into the serve
    # watch directory (transient failures: full disk, slow rename,
    # injected publish_torn chaos)
    publish_retries: int = 5
    # base of the jittered exponential backoff between publish
    # retries (doubles per attempt, capped at 15 s, x[0.5, 1.5)
    # jitter — the init_distributed retry shape)
    publish_backoff_sec: float = 0.25
    # retention: after a successful publish, prune publications
    # beyond this many newest VALID manifests from the publish target
    # (atomic through the store; the currently-served and
    # last-known-good models are never pruned). 0 (default) keeps
    # everything
    publish_keep: int = 0
    # canary validation batch (docs/SERVING.md "Canary gate"): rows
    # embedded in each publication manifest together with the raw
    # scores the publishing model produced for them; a serve replica
    # scores them through its real compiled forest BEFORE swapping
    # and refuses the publication on mismatch. 0 disables the gate
    canary_rows: int = 8
    # absolute tolerance for canary raw-score agreement between the
    # publisher's booster and the replica's compiled forest
    canary_tol: float = 1e-3
    # publish transport target (resilience/store.py): "" (default)
    # publishes into the pipeline's local publish/ directory; a
    # "mem://<name>" spec (tests) or any ArtifactStore-shaped target
    # rides the same manifest-first protocol without a shared
    # filesystem
    publish_store: str = ""

    # ---- convert ----
    convert_model_language: str = ""
    convert_model: str = "gbdt_prediction.cpp"

    # ---- objective ----
    objective_seed: int = 5
    num_class: int = 1
    is_unbalance: bool = False
    scale_pos_weight: float = 1.0
    sigmoid: float = 1.0
    boost_from_average: bool = True
    reg_sqrt: bool = False
    alpha: float = 0.9  # huber / quantile
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    lambdarank_truncation_level: int = 30
    lambdarank_norm: bool = True
    label_gain: List[float] = field(default_factory=list)
    lambdarank_position_bias_regularization: float = 0.0

    # ---- metric ----
    metric: List[str] = field(default_factory=list)
    metric_freq: int = 1
    is_provide_training_metric: bool = False
    eval_at: List[int] = field(default_factory=lambda: [1, 2, 3, 4, 5])
    multi_error_top_k: int = 1
    auc_mu_weights: List[float] = field(default_factory=list)

    # ---- network ----
    num_machines: int = 1
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_file: str = ""
    machines: str = ""
    # deadline (seconds) for every host-level collective of a
    # multi-process run (resilience/watchdog.py): a rank that dies or
    # stalls mid-sync surfaces as a LightGBMError naming the stuck
    # collective instead of an infinite hang. 0 disables; the
    # LIGHTGBM_TPU_COLLECTIVE_TIMEOUT env var overrides
    collective_timeout_sec: float = 300.0

    # ---- tpu-specific (new; no reference analog) ----
    num_devices: int = 0  # 0 = use all visible devices for data-parallel
    hist_dtype: str = "float32"  # histogram accumulator dtype
    # histogram allreduce wire format for distributed training
    # (parallel/comms.py; docs/COLLECTIVES.md): f32 = exact psum |
    # int16 / int8 = EQuARX-style blockwise-quantized allreduce with
    # per-block f32 scales and an error-feedback residual carried
    # through the growth loop (split decisions stay bit-identical
    # across ranks; int8 cuts the dominant data-parallel histogram
    # payload ~4x) | auto = int16 once one f32 histogram reduction
    # crosses ~1 MiB, exact f32 below. Ignored by serial training,
    # feature-parallel (no histogram reduction) and quantized-gradient
    # histograms (already exact int32).
    hist_comm: str = "f32"
    # where the binned training matrix lives (parallel/placement.py;
    # docs/SHARDING.md): "host" keeps the classic host numpy copy and
    # uploads a device copy; "device" lays each rank's binned shard
    # DIRECTLY into its NamedSharding mesh slice
    # (jax.make_array_from_single_device_arrays) and frees the host
    # copy after the upload — no host ever holds the global binned
    # matrix (the gate on datasets whose binned form exceeds one
    # host). "auto" = device when a multi-device mesh is active on an
    # accelerator backend, host otherwise (CPU virtual-device worlds
    # keep host so eager consumers stay cheap; tests opt in
    # explicitly).
    shard_residency: str = "auto"
    # data-parallel split search (ops/grow.py GrowConfig.split_search;
    # docs/SHARDING.md): "gathered" allreduces the full [F, B, 2]
    # histogram and every device searches all features; "sharded"
    # reduce-scatters it so each device searches only its owned F/D
    # feature chunk and the per-device best SplitInfo records are
    # allreduced (the reference DataParallelTreeLearner's
    # ReduceScatter + SyncUpGlobalBestSplit) — post-reduction traffic
    # drops to a 1/D chunk + O(D) split records while split decisions
    # stay byte-identical. Applies to tree_learner=data meshes;
    # feature/voting already shard their searches. EFB-bundled runs
    # fall back to gathered (not covered yet).
    split_search: str = "gathered"
    sharding_axis: str = "data"  # mesh axis name for row sharding
    # histogram build strategy: auto|scatter|mxu|pallas. auto: nibble
    # matmul (MXU) on TPU and scatter-add on CPU; pallas: hand-tiled
    # TPU kernel accumulating the [F, B, 2] histogram in VMEM
    # (ops/pallas_hist.py; runs under the Pallas interpreter on CPU).
    # Flipping auto to pallas on TPU is gated on a measured iters/sec
    # win on the Higgs-shaped bench (LIGHTGBM_TPU_AUTO_PALLAS=1 opts
    # in; see docs/PALLAS.md). Steps down pallas -> mxu -> scatter under
    # the OOM degradation ladder (a recorded fault event); an explicit
    # pallas that cannot be honoured raises.
    hist_method: str = "auto"
    # MXU histogram accumulation passes: default (single-pass bf16 input /
    # f32 accumulation — the reference GPU learner's single-precision
    # histogram choice, docs/GPU-Performance.rst:134-158) | high (3-pass)
    # | highest (6-pass f32 emulation). As compiled for the histogram's
    # matmul those are two and three passes: the one-hot operand's low
    # half is zero and XLA:TPU skips its passes.
    hist_precision: str = "default"
    # tree grower: compact (the flagship: leaf-wise, rows grouped by
    # leaf, per-split work ~ leaf size) | level (DEPTH-wise: the whole
    # frontier splits per step, histograms built in one batched
    # sibling-subtracting pass per level — O(rows) histogram work per
    # LEVEL instead of per split; trees are balanced-by-policy, so
    # they differ from leaf-wise trees whenever the leaf budget binds)
    # | masked (full-row masked histogram passes). "masked" is a
    # deliberately simple CORRECTNESS ORACLE
    # kept for differential testing (tests/test_grower_equivalence.py),
    # not a performance choice: every split pays O(n) histogram work,
    # and it lacks EFB / CEGB / interaction / forced splits /
    # path-smooth / bynode / quantized — configs needing those either
    # auto-upgrade to compact (quantized, forced, bynode, path-smooth;
    # see GBDTBooster.__init__) or raise NotImplementedError
    # (grow_tree_impl), and >50M row*leaf products raise outright.
    # "level" shares masked's feature gating (core set only).
    grower: str = "compact"
    # rows per streaming chunk in the compact grower's partition pass
    # (perf knob; power of two. Larger chunks amortize per-chunk fixed
    # costs but pay more window-tail padding and higher per-row sort
    # depth; no chunk sweep has been run on a local chip — not
    # measured)
    chunk_rows: int = 16384

    # Unrecognized parameters are kept here, not fatal (and not yet
    # warned about: a retired knob such as PR 31's bulk-batching chunk
    # lands here in silence).
    extra: Dict[str, Any] = field(default_factory=dict)

    _BOUNDS = {
        "num_iterations": (0, None),
        "learning_rate": (0.0, None, "gt"),
        "num_leaves": (2, 131072),
        "max_bin": (2, None),
        "min_data_in_bin": (1, None),
        "bin_construct_sample_cnt": (1, None),
        "ingest_chunk_rows": (0, None),
        "min_data_in_leaf": (0, None),
        "min_sum_hessian_in_leaf": (0.0, None),
        "bagging_fraction": (0.0, 1.0, "gt"),
        "pos_bagging_fraction": (0.0, 1.0, "gt"),
        "neg_bagging_fraction": (0.0, 1.0, "gt"),
        "feature_fraction": (0.0, 1.0, "gt"),
        "feature_fraction_bynode": (0.0, 1.0, "gt"),
        "max_delta_step": (None, None),
        "lambda_l1": (0.0, None),
        "lambda_l2": (0.0, None),
        "linear_lambda": (0.0, None),
        "min_gain_to_split": (0.0, None),
        "drop_rate": (0.0, 1.0),
        "skip_drop": (0.0, 1.0),
        "top_rate": (0.0, 1.0),
        "other_rate": (0.0, 1.0),
        "max_cat_threshold": (1, None),
        "cat_l2": (0.0, None),
        "cat_smooth": (0.0, None),
        "max_cat_to_onehot": (1, None),
        "top_k": (1, None),
        "monotone_penalty": (0.0, None),
        "refit_decay_rate": (0.0, 1.0),
        "path_smooth": (0.0, None),
        "sigmoid": (0.0, None, "gt"),
        "alpha": (0.0, None, "gt"),
        "fair_c": (0.0, None, "gt"),
        "poisson_max_delta_step": (0.0, None, "gt"),
        "tweedie_variance_power": (1.0, 2.0),
        "lambdarank_truncation_level": (1, None),
        "num_class": (1, None),
        "scale_pos_weight": (0.0, None, "gt"),
        "num_grad_quant_bins": (2, None),
        "num_machines": (1, None),
        "collective_timeout_sec": (0.0, None),
        "serve_batch_window_ms": (0.0, None),
        "serve_max_batch_rows": (1, None),
        "serve_min_bucket_rows": (1, None),
        "serve_queue_rows": (1, None),
        "serve_stats_interval_sec": (0.0, None, "gt"),
        "serve_watch_interval_sec": (0.0, None, "gt"),
        "serve_shed_queue_rows": (0, None),
        "serve_shed_p99_ms": (0.0, None),
        "serve_shutdown_grace_sec": (0.0, None),
        "serve_min_replicas": (1, None),
        "serve_max_replicas": (0, None),
        "autoscale_up_qps": (0.0, None),
        "autoscale_down_qps": (0.0, None),
        "autoscale_up_p99_ms": (0.0, None),
        "autoscale_up_cooldown_sec": (0.0, None, "gt"),
        "autoscale_down_cooldown_sec": (0.0, None, "gt"),
        "publish_retries": (0, None),
        "publish_backoff_sec": (0.0, None),
        "publish_keep": (0, None),
        "canary_rows": (0, None),
        "canary_tol": (0.0, None, "gt"),
        "metrics_port": (0, 65535),
        "metrics_scrape_interval_sec": (0.0, None),
        "trace_sample_every": (0, None),
        "metric_freq": (1, None),
        "multi_error_top_k": (1, None),
    }

    def __post_init__(self) -> None:
        self.objective = canonical_objective(self.objective)
        if self.boosting in ("gbrt",):
            self.boosting = "gbdt"
        if self.boosting == "goss":
            # legacy spelling: boosting=goss means gbdt + goss sampling
            self.boosting = "gbdt"
            self.data_sample_strategy = "goss"
        if self.boosting == "random_forest":
            self.boosting = "rf"
        if self.boosting not in ("gbdt", "dart", "rf"):
            raise ValueError(f"Unknown boosting type: {self.boosting}")
        if self.data_sample_strategy not in ("bagging", "goss"):
            raise ValueError(
                f"Unknown data_sample_strategy: {self.data_sample_strategy}")
        if self.tree_learner not in ("serial", "feature", "data",
                                     "voting", "auto"):
            raise ValueError(f"Unknown tree_learner: {self.tree_learner}")
        if self.hist_comm not in ("f32", "int16", "int8", "auto"):
            raise ValueError(f"Unknown hist_comm: {self.hist_comm} "
                             "(expected f32, int16, int8 or auto)")
        if self.shard_residency not in ("auto", "host", "device"):
            raise ValueError(
                f"Unknown shard_residency: {self.shard_residency} "
                "(expected auto, host or device)")
        if self.split_search not in ("gathered", "sharded"):
            raise ValueError(
                f"Unknown split_search: {self.split_search} "
                "(expected gathered or sharded)")
        if self.monotone_constraints_method not in (
                "basic", "intermediate", "advanced"):
            raise ValueError(
                f"Unknown monotone_constraints_method: "
                f"{self.monotone_constraints_method}")
        if self.hist_method not in ("auto", "scatter", "mxu", "pallas"):
            raise ValueError(f"Unknown hist_method: {self.hist_method}")
        if self.grower not in ("compact", "masked", "level"):
            raise ValueError(f"Unknown grower: {self.grower}")
        if self.chunk_rows < 256 or (self.chunk_rows
                                     & (self.chunk_rows - 1)) != 0:
            raise ValueError("chunk_rows must be a power of two >= 256, "
                             f"got {self.chunk_rows}")
        if self.hist_precision not in ("default", "high", "highest"):
            raise ValueError(
                f"Unknown hist_precision: {self.hist_precision}")
        if self.nonfinite_policy not in ("raise", "skip_tree", "clamp"):
            raise ValueError(
                f"Unknown nonfinite_policy: {self.nonfinite_policy} "
                "(expected raise, skip_tree or clamp)")
        if self.fused_scan_iters != "auto":
            try:
                self.fused_scan_iters = int(self.fused_scan_iters)
            except (TypeError, ValueError):
                raise ValueError(
                    "fused_scan_iters must be 'auto' or an integer >= 1, "
                    f"got {self.fused_scan_iters!r}") from None
            if not 1 <= self.fused_scan_iters <= 1024:
                raise ValueError(
                    "fused_scan_iters must be in [1, 1024] (one scan "
                    "window is one XLA program; larger windows only "
                    "grow trace time), got "
                    f"{self.fused_scan_iters}")
        for name in ("serve_max_batch_rows", "serve_min_bucket_rows"):
            v = getattr(self, name)
            if v < 1 or (v & (v - 1)) != 0:
                raise ValueError(f"{name} must be a power of two >= 1, "
                                 f"got {v}")
        if self.serve_min_bucket_rows > self.serve_max_batch_rows:
            raise ValueError(
                "serve_min_bucket_rows must be <= serve_max_batch_rows "
                f"({self.serve_min_bucket_rows} > "
                f"{self.serve_max_batch_rows})")
        if self.serve_max_replicas \
                and self.serve_min_replicas > self.serve_max_replicas:
            raise ValueError(
                "serve_min_replicas must be <= serve_max_replicas "
                f"({self.serve_min_replicas} > "
                f"{self.serve_max_replicas})")
        if self.autoscale_up_qps > 0 and self.autoscale_down_qps > 0 \
                and self.autoscale_down_qps >= self.autoscale_up_qps:
            raise ValueError(
                "autoscale_down_qps must stay strictly below "
                "autoscale_up_qps — that gap is the hysteresis band "
                "that stops the fleet flapping "
                f"({self.autoscale_down_qps} >= "
                f"{self.autoscale_up_qps})")
        if self.serve_shed_queue_rows \
                and self.serve_shed_queue_rows >= self.serve_queue_rows:
            raise ValueError(
                "serve_shed_queue_rows (soft shed threshold) must stay "
                "below serve_queue_rows (hard admission wall) to ever "
                f"fire ({self.serve_shed_queue_rows} >= "
                f"{self.serve_queue_rows})")
        for name, spec in self._BOUNDS.items():
            lo, hi = spec[0], spec[1]
            strict = len(spec) > 2 and spec[2] == "gt"
            v = getattr(self, name)
            if v is None:
                continue
            if lo is not None and (v <= lo if strict else v < lo):
                op = ">" if strict else ">="
                raise ValueError(f"{name} = {v} should be {op} {lo}")
            if hi is not None and v > hi:
                raise ValueError(f"{name} = {v} should be <= {hi}")
        if self.objective in ("multiclass", "multiclassova"):
            if self.num_class < 2:
                raise ValueError(
                    "num_class must be >= 2 for multiclass objectives")
        elif self.objective != "custom" and self.num_class != 1:
            raise ValueError(
                f"num_class must be 1 for objective {self.objective}")
        if self.boosting == "rf":
            if not (self.bagging_freq > 0 and 0.0 < self.bagging_fraction < 1.0):
                raise ValueError(
                    "Random forest needs bagging_freq > 0 and "
                    "0 < bagging_fraction < 1")
        if self.is_unbalance and self.scale_pos_weight != 1.0:
            raise ValueError(
                "Cannot set is_unbalance and scale_pos_weight at the same time")

    # -- construction ----------------------------------------------------
    _LIST_INT = {"eval_at", "max_bin_by_feature", "monotone_constraints"}
    _LIST_FLOAT = {"feature_contri", "label_gain", "auc_mu_weights",
                   "cegb_penalty_feature_lazy", "cegb_penalty_feature_coupled"}
    _LIST_STR = {"valid", "metric"}

    @classmethod
    def from_params(cls, params: Optional[Dict[str, Any]]) -> "Config":
        raw = resolve_params(params)
        fields = {f.name: f for f in dataclasses.fields(cls)}
        kwargs: Dict[str, Any] = {}
        extra: Dict[str, Any] = {}
        for k, v in raw.items():
            if k not in fields or k == "extra":
                extra[k] = v
                continue
            f = fields[k]
            try:
                if k in cls._LIST_INT:
                    kwargs[k] = _parse_list(v, int)
                elif k in cls._LIST_FLOAT:
                    kwargs[k] = _parse_list(v, float)
                elif k in cls._LIST_STR:
                    kwargs[k] = _parse_list(v, str)
                elif f.type in ("bool", bool):
                    kwargs[k] = _parse_bool(v)
                elif f.type in ("int", int):
                    kwargs[k] = int(v)
                elif f.type in ("float", float):
                    kwargs[k] = float(v)
                elif f.type in ("Optional[int]",):
                    kwargs[k] = None if v is None else int(v)
                elif k == "categorical_feature" or k == "interaction_constraints":
                    kwargs[k] = v
                else:
                    kwargs[k] = str(v)
            except (TypeError, ValueError) as exc:
                raise ValueError(f"Bad value for parameter {k}: {v!r}") from exc
        cfg = cls(**kwargs)
        cfg.extra = extra
        return cfg

    def to_params(self) -> Dict[str, Any]:
        out = {}
        for f in dataclasses.fields(self):
            if f.name == "extra":
                continue
            out[f.name] = getattr(self, f.name)
        out.update(self.extra)
        return out

    def update(self, params: Dict[str, Any]) -> "Config":
        merged = self.to_params()
        merged.update(resolve_params(params))
        return Config.from_params(merged)

    def to_string(self) -> str:
        parts = []
        for f in dataclasses.fields(self):
            if f.name == "extra":
                continue
            v = getattr(self, f.name)
            if isinstance(v, list):
                v = ",".join(str(x) for x in v)
            parts.append(f"[{f.name}: {v}]")
        return "\n".join(parts)
