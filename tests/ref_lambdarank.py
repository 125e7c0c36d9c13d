"""Plain reference for the ranking objective and metric: a per-query numpy
float64 port of LightGBM v3.3.1's ``LambdarankNDCG`` loop
(src/objective/rank_objective.hpp:139-230, ``GetGradientsForOneQuery``; the
inverse max DCGs of :124-135; the weights of :60-75) and of its NDCG metric
(src/metric/rank_metric.hpp ``Eval``, src/metric/dcg_calculator.cpp
``CalMaxDCGAtK`` / ``CalDCG``).

It shares no code with ``lightgbm_tpu/objective/rank.py`` or
``lightgbm_tpu/utils/dcg.py``: the tests compare the program with it.
Departures from the source, each on purpose: accumulators are float64 where
the source's ``score_t`` is float32; the sigmoid is exact where the source
reads a 1M-entry table of it; the loop over j is one numpy expression per i
(the statements inside it are the source's, in the source's order).
"""
import numpy as np

K_MIN_SCORE = -np.inf


def default_label_gain(n=31):
    return np.array([float((1 << i) - 1) for i in range(n)])


def discount(i):
    return 1.0 / np.log2(2.0 + i)


def cal_max_dcg_at_k(k, label, label_gain):
    """dcg_calculator.cpp CalMaxDCGAtK: greedy from the top label down."""
    ret = 0.0
    label_cnt = np.zeros(len(label_gain), np.int64)
    for v in label:
        label_cnt[int(v)] += 1
    top_label = len(label_gain) - 1
    k = min(k, len(label))
    for j in range(k):
        while top_label > 0 and label_cnt[top_label] <= 0:
            top_label -= 1
        if top_label < 0:
            break
        ret += discount(j) * label_gain[top_label]
        label_cnt[top_label] -= 1
    return ret


def gradients_for_one_query(label, score, inverse_max_dcg, label_gain,
                            sigmoid, norm, truncation_level):
    cnt = len(label)
    lambdas = np.zeros(cnt)
    hessians = np.zeros(cnt)
    # std::stable_sort by score, descending
    sorted_idx = np.argsort(-score, kind="stable")
    best_score = score[sorted_idx[0]]
    worst_idx = cnt - 1
    if worst_idx > 0 and score[sorted_idx[worst_idx]] == K_MIN_SCORE:
        worst_idx -= 1
    worst_score = score[sorted_idx[worst_idx]]
    sum_lambdas = 0.0
    i = 0
    while i < cnt - 1 and i < truncation_level:
        if score[sorted_idx[i]] == K_MIN_SCORE:
            i += 1
            continue
        j = np.arange(i + 1, cnt)
        j = j[score[sorted_idx[j]] != K_MIN_SCORE]
        # skip pairs with the same labels
        j = j[label[sorted_idx[j]] != label[sorted_idx[i]]]
        i_is_high = label[sorted_idx[i]] > label[sorted_idx[j]]
        high_rank = np.where(i_is_high, i, j)
        low_rank = np.where(i_is_high, j, i)
        high = sorted_idx[high_rank]
        low = sorted_idx[low_rank]
        high_label_gain = label_gain[label[high].astype(np.int64)]
        low_label_gain = label_gain[label[low].astype(np.int64)]
        delta_score = score[high] - score[low]
        dcg_gap = high_label_gain - low_label_gain
        paired_discount = np.abs(discount(high_rank) - discount(low_rank))
        delta_pair_ndcg = dcg_gap * paired_discount * inverse_max_dcg
        if norm and best_score != worst_score:
            delta_pair_ndcg = delta_pair_ndcg / (0.01 + np.abs(delta_score))
        with np.errstate(over="ignore"):
            p_lambda = 1.0 / (1.0 + np.exp(sigmoid * delta_score))
        p_hessian = p_lambda * (1.0 - p_lambda)
        p_lambda = p_lambda * (-sigmoid * delta_pair_ndcg)
        p_hessian = p_hessian * (sigmoid * sigmoid * delta_pair_ndcg)
        np.subtract.at(lambdas, low, p_lambda)
        np.add.at(hessians, low, p_hessian)
        np.add.at(lambdas, high, p_lambda)
        np.add.at(hessians, high, p_hessian)
        sum_lambdas -= 2.0 * p_lambda.sum()
        i += 1
    if norm and sum_lambdas > 0:
        norm_factor = np.log2(1.0 + sum_lambdas) / sum_lambdas
        lambdas *= norm_factor
        hessians *= norm_factor
    return lambdas, hessians


def lambdarank_gradients(label, score, query_boundaries, weight=None,
                         label_gain=None, sigmoid=1.0, norm=True,
                         truncation_level=30):
    """(lambdas, hessians) of every row, float64."""
    label = np.asarray(label, np.float64)
    score = np.asarray(score, np.float64)
    label_gain = default_label_gain() if label_gain is None \
        else np.asarray(label_gain, np.float64)
    lambdas = np.zeros(len(label))
    hessians = np.zeros(len(label))
    for a, b in zip(query_boundaries[:-1], query_boundaries[1:]):
        if b == a:
            continue
        inverse_max_dcg = cal_max_dcg_at_k(truncation_level, label[a:b],
                                           label_gain)
        if inverse_max_dcg > 0.0:
            inverse_max_dcg = 1.0 / inverse_max_dcg
        lambdas[a:b], hessians[a:b] = gradients_for_one_query(
            label[a:b], score[a:b], inverse_max_dcg, label_gain, sigmoid,
            norm, truncation_level)
        if weight is not None:
            lambdas[a:b] *= weight[a:b]
            hessians[a:b] *= weight[a:b]
    return lambdas, hessians


def ndcg_at(ks, label, score, query_boundaries, label_gain=None):
    """Mean NDCG@k over the queries for each k (rank_metric.hpp Eval, no
    query weights): a query whose best DCG is 0 counts as 1."""
    label = np.asarray(label, np.float64)
    score = np.asarray(score, np.float64)
    label_gain = default_label_gain() if label_gain is None \
        else np.asarray(label_gain, np.float64)
    result = np.zeros(len(ks))
    num_queries = len(query_boundaries) - 1
    for a, b in zip(query_boundaries[:-1], query_boundaries[1:]):
        lab, sc = label[a:b], score[a:b]
        if cal_max_dcg_at_k(ks[0], lab, label_gain) <= 0.0:
            result += 1.0
            continue
        sorted_idx = np.argsort(-sc, kind="stable")
        for ki, k in enumerate(ks):
            cur_k = min(k, len(lab))
            dcg = 0.0
            for j in range(cur_k):
                dcg += label_gain[int(lab[sorted_idx[j]])] * discount(j)
            result[ki] += dcg / cal_max_dcg_at_k(k, lab, label_gain)
    return result / num_queries
