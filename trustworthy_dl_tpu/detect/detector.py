"""Attack detection — pure in-step verdict math plus host API parity.

The pure layer (``anomaly_verdicts``) reproduces the reference's z-score
pipeline (attack_detector.py:292-342) over BaselineState windows: per-stat
|z| vs the rolling baseline, evidence at z>3, attack iff mean z > 2.5,
confidence = min(1, score/5), with the 10-entry warm-up gate
(attack_detector.py:91,126).  The rule-based attack-type classifier follows
attack_detector.py:350-363 exactly.

The host ``AttackDetector`` class keeps the reference's full public API
(detect_output_anomaly / detect_gradient_poisoning / detect_byzantine_behavior
/ detect_backdoor_attack / update_detection_models / detect_with_ml_models /
statistics / export) for drop-in use, delegating the math to the pure layer.
Unlike the reference, the Byzantine and backdoor checks ARE wired into the
training engine (engine/step.py) — SURVEY §7.5.
"""

from __future__ import annotations

import enum
import json
import logging
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trustworthy_dl_tpu.detect import baseline as bl
from trustworthy_dl_tpu.utils.io import atomic_write_json
from trustworthy_dl_tpu.detect import stats as st
from trustworthy_dl_tpu.detect.baseline import BaselineState

logger = logging.getLogger(__name__)

# Detection thresholds (attack_detector.py:320,330,338,158,179).
EVIDENCE_Z = 3.0       # 3-sigma evidence rule
ANOMALY_SCORE = 2.5    # mean-z attack threshold
CONFIDENCE_SCALE = 5.0
BYZANTINE_SIMILARITY = 0.5
BACKDOOR_KL = 2.0
WARMUP = 10            # min history before verdicts fire
# Small-sample confidence widening: a z-score against a k-sample rolling
# baseline is heavy-tailed for small k, so the verdict threshold scales by
# (1 + K/k) — ~3x at k=4, ~1.16x at k=50, asymptotically the reference's
# constant.  Real attacks score 1-2 orders of magnitude over threshold
# (norm inflation lands at mean-z ≈ 300), so the widening only suppresses
# the early-training flares a constant threshold false-fires on.
SMALL_SAMPLE_WIDEN = 8.0


class AttackType(enum.IntEnum):
    """Attack taxonomy (attack_detector.py:20-26)."""

    DATA_POISONING = 0
    MODEL_POISONING = 1
    GRADIENT_POISONING = 2
    BYZANTINE = 3
    BACKDOOR = 4
    ADVERSARIAL_INPUT = 5

    @property
    def label(self) -> str:
        return self.name.lower()


@dataclass
class AttackDetectionResult:
    """Result of attack detection (attack_detector.py:28-36)."""

    is_attack: bool
    attack_type: Optional[AttackType]
    confidence: float
    evidence: Dict[str, Any]
    timestamp: float
    node_id: int


class Verdicts(NamedTuple):
    """Vectorised detection outcome for all nodes in one step."""

    is_attack: jax.Array      # bool[n]
    attack_type: jax.Array    # i32[n]  AttackType codes (valid iff is_attack)
    confidence: jax.Array     # f32[n]
    score: jax.Array          # f32[n]  mean |z|
    z: jax.Array              # f32[n, S] per-stat |z|
    evidence_mask: jax.Array  # bool[n, S] z > 3


def _rule_hits(z: jax.Array, evidence_mask: jax.Array
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(l2_hit, std_hit, shape_hit) — THE reference rule predicates
    (attack_detector.py:350-363), shared by classify_attack and the
    attribution ladder's _rule_fired so their thresholds can never
    drift apart.  Evidence requires the 3-sigma record first (the
    reference only inspects stats present in the evidence dict)."""
    i_l2 = st.STAT_INDEX["norm_l2"]
    i_std = st.STAT_INDEX["std"]
    i_skew = st.STAT_INDEX["skewness"]
    i_kurt = st.STAT_INDEX["kurtosis"]
    l2_hit = evidence_mask[..., i_l2] & (z[..., i_l2] > 5.0)
    std_hit = evidence_mask[..., i_std] & (z[..., i_std] > 4.0)
    shape_hit = evidence_mask[..., i_skew] | evidence_mask[..., i_kurt]
    return l2_hit, std_hit, shape_hit


def classify_attack(z: jax.Array, evidence_mask: jax.Array) -> jax.Array:
    """Rule-based classifier (attack_detector.py:350-363), vectorised.

    Branch order: norm_l2 z>5 → GRADIENT_POISONING; std z>4 → DATA_POISONING;
    skew/kurtosis evidence → ADVERSARIAL_INPUT; else BYZANTINE.
    """
    l2_hit, std_hit, shape_hit = _rule_hits(z, evidence_mask)
    return jnp.select(
        [l2_hit, std_hit, shape_hit],
        [
            jnp.int32(AttackType.GRADIENT_POISONING),
            jnp.int32(AttackType.DATA_POISONING),
            jnp.int32(AttackType.ADVERSARIAL_INPUT),
        ],
        default=jnp.int32(AttackType.BYZANTINE),
    )


def _rule_fired(z: jax.Array, evidence_mask: jax.Array) -> jax.Array:
    """bool[n]: did any of the reference's classification rules
    (attack_detector.py:350-363) actually trip — as opposed to falling
    through to the default branch?  Same predicates as classify_attack
    (shared via _rule_hits)."""
    l2_hit, std_hit, shape_hit = _rule_hits(z, evidence_mask)
    return l2_hit | std_hit | shape_hit


def attribute_attack(grad_v: "Verdicts", out_v: "Verdicts",
                     byz: jax.Array, backdoor: jax.Array,
                     loss_outlier: Optional[jax.Array] = None) -> jax.Array:
    """i32[n] attack-type attribution ladder (VERDICT r3 weak #7).

    The reference's rule classifier keeps its labels wherever one of its
    rules actually fired (parity, attack_detector.py:350-363); its
    *default* branch — which stamped "byzantine" on every confirmation
    whose fixed z>5/z>4 thresholds hadn't tripped yet, i.e. most FIRST
    detections — is replaced by the explicit consensus checks, the
    loss-detachment signature (a node whose shard loss detached from the
    fleet is training on corrupted DATA), and finally the
    dominant-signature family (classify_attack_dominant)."""
    grad_rule = grad_v.is_attack & _rule_fired(grad_v.z,
                                               grad_v.evidence_mask)
    out_rule = out_v.is_attack & _rule_fired(out_v.z, out_v.evidence_mask)
    if loss_outlier is None:
        loss_outlier = jnp.zeros_like(byz)
    return jnp.select(
        [grad_rule, out_rule, backdoor, byz, loss_outlier],
        [
            grad_v.attack_type,
            out_v.attack_type,
            jnp.full_like(grad_v.attack_type, int(AttackType.BACKDOOR)),
            jnp.full_like(grad_v.attack_type, int(AttackType.BYZANTINE)),
            jnp.full_like(grad_v.attack_type,
                          int(AttackType.DATA_POISONING)),
        ],
        default=classify_attack_dominant(grad_v.z, out_v.z),
    )


def classify_attack_dominant(z_grad: jax.Array, z_out: jax.Array
                             ) -> jax.Array:
    """Best-effort family attribution for confirmations the rule
    classifier cannot label (VERDICT r3 weak #7): when NEITHER battery's
    own verdict fired — the confirmation came from the hard
    cross-sectional outlier, norm-verification, or consensus checks — the
    reference's fixed-threshold rules (z>5 / z>4,
    attack_detector.py:350-363) usually haven't tripped yet, and the
    default branch mislabelled every first detection "byzantine".  Here
    the family whose signature columns carry the dominant z wins:
    gradient-norm columns → GRADIENT_POISONING, dispersion →
    DATA_POISONING, shape (skew/kurtosis) → ADVERSARIAL_INPUT; BYZANTINE
    only when no signature stands out (z < 1), i.e. when the evidence
    genuinely is consensus-only."""
    idx = st.STAT_INDEX
    norm_sig = jnp.maximum(
        jnp.maximum(z_grad[..., idx["norm_l2"]],
                    z_grad[..., idx["norm_l1"]]),
        z_grad[..., idx["norm_inf"]],
    )
    data_sig = jnp.maximum(z_out[..., idx["std"]], z_grad[..., idx["std"]])
    shape_sig = jnp.maximum(
        jnp.maximum(z_out[..., idx["skewness"]],
                    z_out[..., idx["kurtosis"]]),
        jnp.maximum(z_grad[..., idx["skewness"]],
                    z_grad[..., idx["kurtosis"]]),
    )
    fams = jnp.stack([norm_sig, data_sig, shape_sig], axis=-1)
    types = jnp.asarray([
        int(AttackType.GRADIENT_POISONING),
        int(AttackType.DATA_POISONING),
        int(AttackType.ADVERSARIAL_INPUT),
    ], jnp.int32)
    best = jnp.argmax(fams, axis=-1)
    return jnp.where(
        jnp.max(fams, axis=-1) >= 1.0,
        types[best],
        jnp.int32(AttackType.BYZANTINE),
    )


def anomaly_verdicts(
    current_stats: jax.Array,
    state: BaselineState,
    warmup: int = WARMUP,
    score_threshold: float = ANOMALY_SCORE,
) -> Verdicts:
    """Detect statistical anomalies for all nodes ([n, S] current stats vs
    their rolling baselines).  Matches attack_detector.py:292-342 with the
    baseline computed over the window *before* this step's stats are pushed
    (the reference appends first, then builds the baseline including the
    current sample — see ``push_then_detect`` for that exact ordering)."""
    mean, std, valid = bl.baseline_moments(state)
    z = bl.zscores(current_stats, mean, std)
    usable = std > 0
    n_usable = jnp.maximum(jnp.sum(usable, axis=-1), 1)
    score = jnp.sum(jnp.where(usable, z, 0.0), axis=-1) / n_usable
    warm = valid >= warmup
    threshold_eff = score_threshold * (
        1.0 + SMALL_SAMPLE_WIDEN / jnp.maximum(valid.astype(jnp.float32), 1.0)
    )
    is_attack = (score > threshold_eff) & warm
    evidence = (z > EVIDENCE_Z) & usable
    return Verdicts(
        is_attack=is_attack,
        attack_type=classify_attack(z, evidence),
        confidence=jnp.minimum(1.0, score / CONFIDENCE_SCALE),
        score=score,
        z=z,
        evidence_mask=evidence,
    )


def push_then_detect(
    state: BaselineState,
    current_stats: jax.Array,
    mask: Optional[jax.Array] = None,
    warmup: int = WARMUP,
    score_threshold: float = ANOMALY_SCORE,
) -> Tuple[BaselineState, Verdicts]:
    """Reference ordering: append this step's stats to history, rebuild the
    baseline over the window (now containing the current sample), then score
    (attack_detector.py:84-100,119-135)."""
    state = bl.push_stats(state, current_stats, mask)
    verdicts = anomaly_verdicts(current_stats, state, warmup, score_threshold)
    if mask is not None:
        verdicts = verdicts._replace(
            is_attack=verdicts.is_attack & mask.astype(bool)
        )
    return state, verdicts


# ---------------------------------------------------------------------------
# Host-facing API (reference parity: attack_detector.py:38-487)
# ---------------------------------------------------------------------------


class AttackDetector:
    """Comprehensive attack detection system for distributed training."""

    def __init__(self, detection_threshold: float = 0.8, history_size: int = 1000,
                 exact_order_stats: bool = True):
        self.detection_threshold = detection_threshold
        self.history_size = history_size
        self.exact_order_stats = exact_order_stats

        self.output_history: Dict[int, deque] = defaultdict(
            lambda: deque(maxlen=history_size)
        )
        self.gradient_history: Dict[int, deque] = defaultdict(
            lambda: deque(maxlen=history_size)
        )
        self.loss_history: Dict[int, deque] = defaultdict(
            lambda: deque(maxlen=history_size)
        )
        self.output_baselines: Dict[int, Dict] = defaultdict(dict)
        self.gradient_baselines: Dict[int, Dict] = defaultdict(dict)
        self.anomaly_detectors: Dict[int, Any] = {}
        self.clustering_models: Dict[int, Any] = {}
        self._model_keys: Dict[int, list] = {}  # fit-time feature order
        self.detection_stats = {
            "total_detections": 0,
            "false_positives": 0,
            "true_positives": 0,
            "attack_types": defaultdict(int),
        }
        logger.info("AttackDetector initialized")

    # -- stats helpers ---------------------------------------------------

    def _stats_dict(self, names: Sequence[str], values: np.ndarray) -> Dict[str, float]:
        return {name: float(v) for name, v in zip(names, values)}

    def calculate_tensor_statistics(self, tensor: Any) -> Dict[str, float]:
        """12-stat dict (attack_detector.py:185-200)."""
        arr = jnp.asarray(np.asarray(tensor), jnp.float32)
        vals = np.asarray(st.tensor_statistics(arr, self.exact_order_stats))
        return self._stats_dict(st.TENSOR_STAT_NAMES, vals)

    def calculate_gradient_statistics(self, gradients: Sequence[Any]) -> Dict[str, float]:
        """17-stat dict (attack_detector.py:202-223)."""
        if not gradients:
            return {}
        grads = [jnp.asarray(np.asarray(g), jnp.float32) for g in gradients]
        vals = np.asarray(st.gradient_statistics(grads, self.exact_order_stats))
        return self._stats_dict(st.GRADIENT_STAT_NAMES, vals)

    # -- detection entry points (reference API) --------------------------

    def detect_output_anomaly(self, output: Any, node_id: int, step: int) -> bool:
        """attack_detector.py:71-107."""
        if output is None:
            return True
        stats_d = self.calculate_tensor_statistics(output)
        self.output_history[node_id].append(
            {"step": step, "stats": stats_d, "timestamp": time.time()}
        )
        if len(self.output_history[node_id]) < WARMUP:
            return False
        self._update_baseline(node_id, self.output_history, self.output_baselines)
        result = self._detect_statistical_anomaly(
            stats_d, self.output_baselines[node_id], node_id
        )
        if result.is_attack:
            logger.warning(
                "Output anomaly detected on node %d: %s", node_id, result.attack_type
            )
            self.detection_stats["total_detections"] += 1
            self.detection_stats["attack_types"][result.attack_type.label] += 1
        return result.is_attack

    def detect_gradient_poisoning(self, gradients: Sequence[Any], node_id: int,
                                  step: int) -> bool:
        """attack_detector.py:109-141."""
        if gradients is None or len(gradients) == 0:
            return False
        stats_d = self.calculate_gradient_statistics(gradients)
        self.gradient_history[node_id].append(
            {"step": step, "stats": stats_d, "timestamp": time.time()}
        )
        if len(self.gradient_history[node_id]) < WARMUP:
            return False
        self._update_baseline(node_id, self.gradient_history, self.gradient_baselines)
        result = self._detect_statistical_anomaly(
            stats_d, self.gradient_baselines[node_id], node_id
        )
        if result.is_attack:
            logger.warning("Gradient poisoning detected on node %d", node_id)
            self.detection_stats["total_detections"] += 1
        return result.is_attack

    def detect_byzantine_behavior(self, node_outputs: Dict[int, Any], step: int
                                  ) -> List[int]:
        """Cross-node pairwise-similarity outlier check
        (attack_detector.py:143-162)."""
        if len(node_outputs) < 3:
            return []
        ids = sorted(node_outputs)
        flat = [np.asarray(node_outputs[i], np.float32).reshape(-1) for i in ids]
        lengths = {f.shape[0] for f in flat}
        if len(lengths) == 1 and 0 not in lengths:
            # Equal shapes — the reference's only case (attack_detector.py:
            # 365-379) and the common one: single vectorized device call.
            verdicts = np.asarray(
                st.byzantine_verdicts(jnp.asarray(np.stack(flat)),
                                      BYZANTINE_SIMILARITY)
            )
        else:
            # Ragged outputs (this build's extension): each pair's dot runs
            # over its common prefix but is normalised by both FULL norms —
            # mass outside the shared support cannot be cross-checked, so
            # it counts AGAINST its owner.  This is the only assignment of
            # the unverifiable tail that is Byzantine-safe: a global
            # truncation width hands the shortest node control of every
            # pair's support, a plain per-pair prefix cosine lets an
            # attacker echo an honest prefix and hide a payload in the
            # suffix, and a near-empty output scores ~0 here rather than
            # shrinking anyone else's comparison.
            n = len(flat)
            norms = np.array([np.linalg.norm(f) for f in flat])
            sims = np.zeros((n, n), np.float64)
            for a in range(n):
                for c in range(a + 1, n):
                    w = min(flat[a].shape[0], flat[c].shape[0])
                    denom = norms[a] * norms[c]
                    s = float(flat[a][:w] @ flat[c][:w] / denom) \
                        if w and denom > 0 else 0.0
                    sims[a, c] = sims[c, a] = s
            mean_sim = sims.sum(axis=1) / (n - 1)
            verdicts = mean_sim < BYZANTINE_SIMILARITY
        byzantine = [i for i, flag in zip(ids, verdicts) if flag]
        for node_id in byzantine:
            logger.warning("Byzantine behavior detected on node %d", node_id)
        return byzantine

    def detect_backdoor_attack(self, model_outputs: Any, expected_outputs: Any,
                               node_id: int) -> bool:
        """KL-divergence backdoor check (attack_detector.py:164-183)."""
        if model_outputs is None or expected_outputs is None:
            return False
        flagged = bool(
            st.detect_backdoor(
                jnp.asarray(np.asarray(model_outputs), jnp.float32),
                jnp.asarray(np.asarray(expected_outputs), jnp.float32),
                BACKDOOR_KL,
            )
        )
        if flagged:
            logger.warning("Potential backdoor attack detected on node %d", node_id)
        return flagged

    # -- baseline & scoring ---------------------------------------------

    def _update_baseline(self, node_id: int, history: Dict[int, deque],
                         baselines: Dict[int, Dict]) -> None:
        """Window aggregate per stat (attack_detector.py:241-290)."""
        entries = list(history[node_id])
        if len(entries) < WARMUP:
            return
        agg: Dict[str, List[float]] = defaultdict(list)
        for entry in entries:
            for name, value in entry["stats"].items():
                agg[name].append(value)
        baselines[node_id] = {
            name: {
                "mean": float(np.mean(vals)),
                "std": float(np.std(vals)),
                "min": float(np.min(vals)),
                "max": float(np.max(vals)),
                "percentile_5": float(np.percentile(vals, 5)),
                "percentile_95": float(np.percentile(vals, 95)),
            }
            for name, vals in agg.items()
        }

    def _detect_statistical_anomaly(self, current_stats: Dict[str, float],
                                    baseline: Dict[str, Dict], node_id: int
                                    ) -> AttackDetectionResult:
        """attack_detector.py:292-342."""
        if not baseline:
            return AttackDetectionResult(False, None, 0.0, {}, time.time(), node_id)
        scores = []
        evidence: Dict[str, Any] = {}
        for name, value in current_stats.items():
            base = baseline.get(name)
            if base is None or base["std"] <= 0:
                continue
            z = abs((value - base["mean"]) / base["std"])
            scores.append(z)
            if z > EVIDENCE_Z:
                evidence[name] = {
                    "z_score": z,
                    "current_value": value,
                    "baseline_mean": base["mean"],
                    "baseline_std": base["std"],
                }
        overall = float(np.mean(scores)) if scores else 0.0
        is_attack = overall > ANOMALY_SCORE
        attack_type = self._classify_attack_type(evidence)
        return AttackDetectionResult(
            is_attack=is_attack,
            attack_type=attack_type if is_attack else None,
            confidence=min(1.0, overall / CONFIDENCE_SCALE),
            evidence=evidence,
            timestamp=time.time(),
            node_id=node_id,
        )

    def _classify_attack_type(self, evidence: Dict) -> Optional[AttackType]:
        """attack_detector.py:350-363."""
        if not evidence:
            return None
        if "norm_l2" in evidence and evidence["norm_l2"]["z_score"] > 5:
            return AttackType.GRADIENT_POISONING
        if "std" in evidence and evidence["std"]["z_score"] > 4:
            return AttackType.DATA_POISONING
        if "skewness" in evidence or "kurtosis" in evidence:
            return AttackType.ADVERSARIAL_INPUT
        return AttackType.BYZANTINE

    # -- ML-model path (attack_detector.py:381-425) ----------------------

    # Hyperparameters pinned to the reference's values so verdicts are
    # comparable (attack_detector.py:388-397); the surrounding machinery —
    # feature ordering, refit cadence, unsupported-env gating — is ours.
    ML_MIN_SAMPLES = 50
    ML_ISOFOREST_KW = dict(contamination=0.1, random_state=42, n_estimators=100)
    ML_DBSCAN_KW = dict(eps=0.5, min_samples=5)

    @staticmethod
    def _joined_stats(out_entry: Optional[Dict],
                      grad_entry: Optional[Dict]) -> Dict[str, float]:
        """One feature row from the output battery and (when present) the
        gradient battery, namespaced so the two 17-stat dicts can't
        collide."""
        row: Dict[str, float] = {}
        if out_entry is not None:
            row.update({f"out:{k}": v for k, v in out_entry["stats"].items()})
        if grad_entry is not None:
            row.update({f"grad:{k}": v for k, v in grad_entry["stats"].items()})
        return row

    def _node_feature_matrix(self, node_id: int) -> Optional[tuple]:
        """(keys, [t, d] matrix) of one node's joined stat-battery history
        (output AND gradient batteries — the engine appends both once per
        step), with a stable (sorted-key) column order.  The keys are
        stored with the fitted model so inference indexes the query dict in
        fit-time order.  Histories of unequal length (host-API standalone
        use appends only one stream) are aligned at their newest entries."""
        out_h = self.output_history.get(node_id)
        if out_h is None or len(out_h) < self.ML_MIN_SAMPLES:
            return None
        # Deques index in O(n): materialise once so the join stays O(t).
        grad_h = list(self.gradient_history.get(node_id) or ())
        offset = len(out_h) - len(grad_h)
        joined = [
            self._joined_stats(
                entry,
                grad_h[i - offset] if 0 <= i - offset < len(grad_h) else None,
            )
            for i, entry in enumerate(out_h)
        ]
        keys = sorted(joined[-1])
        return keys, np.asarray(
            [[row.get(k, 0.0) for k in keys] for row in joined],
            dtype=np.float64,
        )

    def latest_features(self, node_id: int) -> Optional[Dict[str, float]]:
        """The newest joined feature row — what detect_with_ml_models should
        score at epoch cadence."""
        out_h = self.output_history.get(node_id)
        if not out_h:
            return None
        grad_h = self.gradient_history.get(node_id)
        return self._joined_stats(out_h[-1], grad_h[-1] if grad_h else None)

    def update_detection_models(self, fit_clustering: bool = False) -> int:
        """Refit the per-node unsupervised detectors at epoch cadence; a
        no-op on nodes without enough history or when sklearn is absent.
        Returns the rows fitted, summed over the nodes.

        ``fit_clustering`` also refits the per-node DBSCAN models.  Off by
        default as a deliberate deviation: the reference fits DBSCAN on
        every update but no code path (theirs or ours) ever queries it
        (attack_detector.py:395-397 — the 'defined but never called'
        disease, SURVEY §7.5), and the O(t²) fit over 1000x17 histories is
        the dominant cost of the ML tier."""
        try:
            from sklearn.cluster import DBSCAN
            from sklearn.ensemble import IsolationForest
        except ImportError:
            logger.debug("detect: no sklearn in env, ML tier stays off")
            return 0
        fitted = rows = 0
        for node_id in list(self.output_history):
            features = self._node_feature_matrix(node_id)
            if features is None:
                continue
            keys, matrix = features
            self._model_keys[node_id] = keys
            self.anomaly_detectors[node_id] = IsolationForest(
                **self.ML_ISOFOREST_KW
            ).fit(matrix)
            if fit_clustering:
                self.clustering_models[node_id] = DBSCAN(
                    **self.ML_DBSCAN_KW
                ).fit(matrix)
            fitted += 1
            rows += len(matrix)
        if fitted:
            logger.info("detect: refit ML detectors for %d node(s)", fitted)
        return rows

    def detect_with_ml_models(self, stats: Dict[str, float], node_id: int) -> bool:
        """Score one stat vector against the node's fitted IsolationForest;
        False when no model exists yet (warm-up / sklearn-less env)."""
        model = self.anomaly_detectors.get(node_id)
        if model is None:
            return False
        if stats and not any(":" in k for k in stats):
            # Raw (un-namespaced) battery dict from the standalone host
            # path: it is an output battery by contract.
            stats = {f"out:{k}": v for k, v in stats.items()}
        keys = self._model_keys.get(node_id) or sorted(stats)
        vec = np.asarray(
            [stats.get(k, 0.0) for k in keys], dtype=np.float64
        )[None, :]
        verdict = bool(model.predict(vec)[0] == -1)
        if verdict:
            logger.debug(
                "detect: ML verdict anomalous for node %d (score=%.4f)",
                node_id,
                float(model.decision_function(vec)[0]),
            )
        return verdict

    # -- statistics / maintenance (attack_detector.py:427-487) -----------

    def get_detection_statistics(self) -> Dict:
        total = self.detection_stats["total_detections"]
        return {
            "total_detections": total,
            "false_positive_rate": self.detection_stats["false_positives"]
            / max(1, total),
            "true_positive_rate": self.detection_stats["true_positives"]
            / max(1, total),
            "attack_type_distribution": dict(self.detection_stats["attack_types"]),
            "nodes_monitored": len(self.output_history),
            "average_history_length": float(
                np.mean([len(h) for h in self.output_history.values()])
            )
            if self.output_history
            else 0,
        }

    def set_detection_threshold(self, threshold: float) -> None:
        self.detection_threshold = float(np.clip(threshold, 0.0, 1.0))
        logger.info("Detection threshold updated to %s", self.detection_threshold)

    def reset_node_history(self, node_id: int) -> None:
        if node_id in self.output_history:
            self.output_history[node_id].clear()
        if node_id in self.gradient_history:
            self.gradient_history[node_id].clear()
        self.output_baselines.pop(node_id, None)
        self.gradient_baselines.pop(node_id, None)
        logger.info("Detection history reset for node %d", node_id)

    def export_detection_data(self, filepath: str) -> None:
        export_data = {
            "detection_stats": {
                **{k: v for k, v in self.detection_stats.items() if k != "attack_types"},
                "attack_types": dict(self.detection_stats["attack_types"]),
            },
            "baselines": {
                "output": {str(k): v for k, v in self.output_baselines.items()},
                "gradient": {str(k): v for k, v in self.gradient_baselines.items()},
            },
            "history_lengths": {
                str(node_id): len(history)
                for node_id, history in self.output_history.items()
            },
        }
        atomic_write_json(filepath, export_data)
        logger.info("Detection data exported to %s", filepath)

    def cleanup(self) -> None:
        self.output_history.clear()
        self.gradient_history.clear()
        self.loss_history.clear()
        self.anomaly_detectors.clear()
        self.clustering_models.clear()
        logger.info("AttackDetector cleanup completed")
