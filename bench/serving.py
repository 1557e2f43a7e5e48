"""What the serving drivers share: the engine over factor tables drawn from
the seed, the per-layer readers' context, and the readings the limits are
calibrated from.  The engine runs at ``ServingEngine``'s defaults.
"""
from __future__ import annotations

import numpy as np

from bench import checks, data, reference, work


def check(cfg: dict, traffic: dict) -> None:
    """The engine reads nothing of the configuration but its sizes, rank
    and pruning rate, which the reference follows."""


def tables(cfg: dict, seed: int):
    """(P, Q, t_p, t_q): the tables drawn from ``seed`` and the thresholds
    of Eq. 7/8 at the configuration's pruning rate."""
    p, q = data.factor_tables(cfg, seed)
    return (p, q, reference.table_threshold(p, cfg["pruning_rate"]),
            reference.table_threshold(q, cfg["pruning_rate"]))


def engine(cfg: dict, seed: int):
    """The engine the window drives, its tables, and the traffic's seed."""
    from repro.core.mf import MFParams
    from repro.serving.engine import ServingEngine

    s_tables, s_traffic = data.sub_seeds(seed, 2)
    drawn = tables(cfg, s_tables)
    p, q, t_p, t_q = drawn
    params = MFParams(p=p, q=q, user_bias=None, item_bias=None,
                      global_mean=None, implicit=None)
    return ServingEngine(params, t_p, t_q), drawn, s_traffic


def ctx(drawn, users_served, launches) -> dict:
    """Rank histograms behind the scoring work of the served users."""
    p, q, t_p, t_q = drawn
    k = p.shape[1]
    r_u = np.asarray(reference.ranks(p, t_p))
    h_item = work.histogram(reference.ranks(q, t_q), k)
    return {"user_ranks": r_u[users_served], "h_item": h_item, "launches": launches}


def controls(cfg: dict, traffic: dict, seed: int):
    """At the cell's own size, for users sampled from the seed: the
    reference at Precision.HIGH's three bfloat16 passes in the program's
    place, against the float32-exact reference; and two faults planted in
    the exact reference's answers: each answer's items shifted by one id,
    and the second half of the users answered with the first half's rows."""
    s_tables, _ = data.sub_seeds(seed, 2)
    drawn = tables(cfg, s_tables)
    n = traffic.get("sample_users", traffic.get("sample_requests"))
    users = np.random.default_rng(seed).choice(cfg["num_users"], n, replace=False)
    users = users.astype(np.int32)
    topk = traffic["topk"]
    first = np.zeros((n, topk), np.int32)
    _, _, _, top_s, top_i = checks.reference_blocks(
        drawn, users, first, topk=topk, precision="bf16_3x")
    _, _, _, ex_s, ex_i = checks.reference_blocks(
        drawn, users, first, topk=topk, precision="highest")
    pick = np.arange(n) % (n // 2)
    return {
        "control_bf16_3x": checks.topk_gaps(drawn, users, top_s, top_i, topk=topk),
        "fault_altered": checks.topk_gaps(
            drawn, users, ex_s, (ex_i + 1) % cfg["num_items"], topk=topk),
        "fault_half_users": checks.topk_gaps(
            drawn, users, ex_s[pick], ex_i[pick], topk=topk),
    }
