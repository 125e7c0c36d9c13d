"""The benchmark's own scorer for models with categorical splits:
``harness/reference.py``'s ``flatten`` and ``walk`` with ``==`` nodes.
The comparison that decides ``correct`` never goes through the program's
predictor.

A categorical node of ``Booster.dump_model()`` has ``decision_type``
``"=="`` and, as in the reference's dump (``tree.cpp`` ``NodeToJSON``),
``threshold`` the list of the category values that go LEFT, joined by
``||`` ("3||17||42"). Everything else goes right: a category that is not
in the list (unseen in training, or too rare for a bin of its own), a
negative value, NaN (``tree.h`` ``CategoricalDecision``). Numerical nodes
are routed as ``reference.walk`` routes them.

A program from before the dump carried the lists writes the node's index
into the model file's bitsets there (a number); ``flatten`` then takes the
lists from ``model_text`` (``Booster.model_to_string()``: ``cat_boundaries``
and ``cat_threshold``, 32 categories a word), so that both sides of a
comparison are scored by the same walk.
"""
from __future__ import annotations

import numpy as np

from .reference import _MISSING, rank_auc, sigmoid  # noqa: F401 (re-export)


def _bitsets_of(model_text: str) -> list:
    """Per tree of a model file: (cat_boundaries, cat_threshold) as int
    lists, or None for a tree with no categorical node."""
    out = []
    for block in model_text.split("\nTree=")[1:]:
        kv = dict(line.split("=", 1) for line in block.splitlines()
                  if "=" in line)
        out.append(([int(x) for x in kv["cat_boundaries"].split()],
                    [int(x) for x in kv["cat_threshold"].split()])
                   if "cat_boundaries" in kv else None)
    return out


def _categories(node: dict, bitsets) -> np.ndarray:
    thr = node["threshold"]
    if isinstance(thr, str):
        return np.array(sorted(int(c) for c in thr.split("||") if c),
                        np.int64)
    if bitsets is None:
        raise ValueError("a categorical node without its category list, "
                         "and no model text to take the bitset from")
    bounds, words = bitsets
    lo, hi = bounds[int(thr)], bounds[int(thr) + 1]
    return np.array([32 * w + b for w, word in enumerate(words[lo:hi])
                     for b in range(32) if (word >> b) & 1], np.int64)


def flatten(dump: dict, model_text: str = "") -> list:
    """``reference.flatten``'s arrays per tree, and ``categories``: per
    internal node the sorted category values that go left (None for a
    numerical node). Iterative, so depth is no limit."""
    bitsets = _bitsets_of(model_text) if model_text else None
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
             "categories": [None] * n_int,
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
            i = int(node["split_index"])
            t["feature"][i] = node["split_feature"]
            if node["decision_type"] == "==":
                t["categories"][i] = _categories(
                    node, bitsets[int(info["tree_index"])]
                    if bitsets else None)
            elif node["decision_type"] == "<=":
                t["threshold"][i] = node["threshold"]
            else:
                raise ValueError(f"decision_type {node['decision_type']!r}")
            t["default_left"][i] = node["default_left"]
            t["missing"][i] = _MISSING[node["missing_type"]]
            t["left"][i] = child_id(node["left_child"])
            t["right"][i] = child_id(node["right_child"])
            stack += [node["left_child"], node["right_child"]]
        trees.append(t)
    return trees


def categorical_share(trees: list) -> float:
    """Categorical internal nodes over all internal nodes."""
    cat = sum(c is not None for t in trees for c in t["categories"])
    return cat / max(sum(t["feature"].size for t in trees), 1)


def walk(trees: list, X: np.ndarray) -> np.ndarray:
    """Raw score (sum of leaf values) of each row of X, float64. Rows are
    partitioned node by node, so each decision reads one feature's
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
            cats = t["categories"][i]
            if cats is not None:
                # NaN and negative values go right; the value is truncated
                # toward zero as the reference's static_cast<int> does
                code = np.where(np.isnan(v) | (v < 0), -1, v).astype(np.int64)
                left = np.isin(code, cats)
            else:
                left = np.where(np.isnan(v), 0.0, v) <= t["threshold"][i]
                miss = t["missing"][i]
                if miss:
                    gone = (np.isnan(v) if miss == 2
                            else np.isnan(v) | (v == 0))
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
