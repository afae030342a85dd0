"""What a traffic driver's check returns: the numbers compared, each with its
limit (a run is correct when every number is at most its limit), and
the units of work attempted and failed."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass
class Checked:
    checks: dict[str, dict]
    attempted: int
    failed: int


def number(value, limit) -> dict:
    return {"value": value, "limit": limit}
