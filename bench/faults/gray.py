"""A gray link: the queue loses each packet with probability
``loss_p``, drawn from the lane's seed."""
from __future__ import annotations


def apply(out: dict, q: int, fault: dict) -> None:
    out["loss_p"][q] = fault["loss_p"]
