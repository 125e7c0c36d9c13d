"""The benchmark's own scorer and metric: the comparison that decides
``correct`` never goes through the program's predictor or its metrics.

``flatten`` turns ``Booster.dump_model()``'s nested trees into arrays,
``walk`` routes float32 rows through them in numpy (float64 compares, as
the reference's host walk does), ``rank_auc`` is the Mann-Whitney AUC
with tied scores sharing their mean rank.
"""
from __future__ import annotations

import numpy as np

_MISSING = {"None": 0, "Zero": 1, "NaN": 2}


def flatten(dump: dict) -> list:
    """One dict of arrays per tree of a ``dump_model()`` result: internal
    nodes ``feature``, ``threshold``, ``default_left``, ``missing``,
    ``left``, ``right`` (children >= 0 are internal nodes, < 0 are
    ``~leaf``) and ``leaf_value``. Iterative, so depth is no limit."""
    trees = []
    for info in dump["tree_info"]:
        root = info["tree_structure"]
        n_leaves = int(info["num_leaves"])
        n_int = max(n_leaves - 1, 0)
        t = {"feature": np.zeros(n_int, np.int32),
             "threshold": np.zeros(n_int, np.float64),
             "default_left": np.zeros(n_int, bool),
             "missing": np.zeros(n_int, np.int8),
             "left": np.zeros(n_int, np.int32),
             "right": np.zeros(n_int, np.int32),
             "leaf_value": np.zeros(max(n_leaves, 1), np.float64)}

        def child_id(node):
            return (~int(node["leaf_index"]) if "leaf_index" in node
                    else int(node["split_index"]))

        stack = [root]
        while stack:
            node = stack.pop()
            if "split_index" not in node:
                t["leaf_value"][int(node.get("leaf_index", 0))] = \
                    node["leaf_value"]
                continue
            if node["decision_type"] != "<=":
                raise ValueError("the benchmark's walk handles numerical "
                                 "splits only; add categorical routing to "
                                 "a new reference before using such a cell")
            i = int(node["split_index"])
            t["feature"][i] = node["split_feature"]
            t["threshold"][i] = node["threshold"]
            t["default_left"][i] = node["default_left"]
            t["missing"][i] = _MISSING[node["missing_type"]]
            t["left"][i] = child_id(node["left_child"])
            t["right"][i] = child_id(node["right_child"])
            stack += [node["left_child"], node["right_child"]]
        trees.append(t)
    return trees


def walk(trees: list, X: np.ndarray) -> np.ndarray:
    """Raw score (sum of leaf values) of each row of X, float64. Rows are
    partitioned node by node, so each compare reads one feature's
    contiguous column."""
    n = X.shape[0]
    XT = np.ascontiguousarray(X.T)
    raw = np.zeros(n, np.float64)
    everyone = np.arange(n, dtype=np.int64)
    for t in trees:
        if t["feature"].size == 0:
            raw += t["leaf_value"][0]
            continue
        stack = [(0, everyone)]
        while stack:
            i, rows = stack.pop()
            v = XT[t["feature"][i]][rows].astype(np.float64)
            left = np.where(np.isnan(v), 0.0, v) <= t["threshold"][i]
            miss = t["missing"][i]
            if miss:
                gone = np.isnan(v) if miss == 2 else np.isnan(v) | (v == 0)
                left = np.where(gone, t["default_left"][i], left)
            for child, part in ((t["left"][i], rows[left]),
                                (t["right"][i], rows[~left])):
                if part.size == 0:
                    continue
                if child < 0:
                    raw[part] += t["leaf_value"][~child]
                else:
                    stack.append((int(child), part))
    return raw


def sigmoid(raw: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-raw))


def rank_auc(label: np.ndarray, score: np.ndarray) -> float:
    """Area under the ROC curve from ranks; ties share their mean rank."""
    label = np.asarray(label) > 0
    order = np.argsort(score, kind="stable")
    s = np.asarray(score)[order]
    ranks = np.empty(s.size, np.float64)
    # mean rank of each run of equal scores
    edge = np.flatnonzero(np.r_[True, s[1:] != s[:-1], True])
    mean_rank = (edge[:-1] + edge[1:] + 1) / 2.0
    ranks[order] = np.repeat(mean_rank, np.diff(edge))
    pos = int(label.sum())
    neg = label.size - pos
    if pos == 0 or neg == 0:
        raise ValueError("AUC needs both classes")
    return float((ranks[label].sum() - pos * (pos + 1) / 2.0) / (pos * neg))
