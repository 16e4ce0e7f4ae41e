"""Prometheus text exposition -> {sample name: value summed over labels}."""

from __future__ import annotations


def parse(text: str) -> dict[str, float]:
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0].strip()
        if name.endswith("_bucket") or name.endswith("_created"):
            continue
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def delta(before: dict, after: dict, name: str) -> float | None:
    """after - before of one sample, None if the program does not export it."""
    if name not in after:
        return None
    return after[name] - before.get(name, 0.0)
