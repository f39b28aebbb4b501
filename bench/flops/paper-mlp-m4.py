"""Operations one round of the asynchronous cascaded protocol needs on
the paper's tabular MLP, from its shapes, at 2 FLOP per multiply-add.

A round with one active party and batch B: the party's clean forward
and q perturbed forwards (B x f x e each); the server's forward and
backward on the clean batch (backward at twice the forward); and q + 1
server forwards to read the lane losses. Bias adds, ReLUs and the
softmax are left out (under 1 % of the total).
"""
from __future__ import annotations


def client_forward_flops(cfg: dict, batch: int) -> float:
    f = cfg["n_features"] // cfg["n_clients"]
    return 2.0 * batch * f * cfg["client_embed"]


def server_forward_flops(cfg: dict, batch: int) -> float:
    concat = cfg["n_clients"] * cfg["client_embed"]
    se, C = cfg["server_embed"], cfg["n_classes"]
    return 2.0 * batch * (concat * se + se * C)


def round_flops(cfg: dict, batch: int, q: int) -> float:
    return ((1 + q) * client_forward_flops(cfg, batch)
            + 3 * server_forward_flops(cfg, batch)
            + (1 + q) * server_forward_flops(cfg, batch))
