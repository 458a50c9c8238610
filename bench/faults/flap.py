"""A link flap: the queue drops every packet from ``fail_at`` until
``heal_at``."""
from __future__ import annotations


def apply(out: dict, q: int, fault: dict) -> None:
    out["fail_at"][q], out["heal_at"][q] = fault["fail_at"], fault["heal_at"]
