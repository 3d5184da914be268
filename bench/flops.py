"""Operations the algorithm needs, counted from shapes.

Only the matrix multiplications of the LSTM local step count: the
forward pass of each sample (input and recurrent projections at every
timestep, then the linear head) and its backward pass at twice that.
Gossip, the optimizer, elementwise gate math and recomputed work are not
counted.
"""
from __future__ import annotations


def lstm_forward_flops(hidden: int, history_len: int, input_size: int = 1) -> int:
    """FLOPs of one sample's forward pass: per timestep one
    ``(I + H) x 4H`` projection, then the ``H x 1`` head."""
    gates = 2 * (input_size + hidden) * 4 * hidden * history_len
    head = 2 * hidden
    return gates + head


def lstm_train_flops_per_node_step(
    hidden: int, history_len: int, batch_size: int, input_size: int = 1
) -> int:
    """Forward and backward (3 x forward) over one local batch."""
    return 3 * batch_size * lstm_forward_flops(hidden, history_len, input_size)


def federation_round_flops(config: dict, inactive_ratio: float) -> float:
    """FLOPs one federation round needs: the local step of the expected
    number of ACTIVE nodes, ``(1 - inactive) * N``.  Inactive nodes' work
    is not needed, so a program that skips it is credited."""
    per_node = lstm_train_flops_per_node_step(
        config["hidden"], config["history_len"], config["batch_size"],
        config["input_size"],
    ) * config["local_steps"]
    return (1.0 - inactive_ratio) * config["num_nodes"] * per_node
