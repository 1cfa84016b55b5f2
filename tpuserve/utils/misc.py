"""Small shared helpers used across the framework."""

from __future__ import annotations


def env_flag(name: str, default: bool = True) -> bool:
    """Boolean env-var parse shared by every consumer of a given flag —
    ONE definition of falsiness ("0"/"false"/"off"), so sites like
    ``TPUSERVE_HOST_BATCHED`` (engine emit batching, scheduler admission,
    profiler labelling) can never resolve the same process-wide flag
    differently and silently split an A/B lever."""
    import os
    val = os.environ.get(name)
    if val is None:
        return default
    return val.strip().lower() not in ("0", "false", "off", "no")


def cdiv(a: int, b: int) -> int:
    """Ceiling division."""
    return -(-a // b)


def round_up(x: int, multiple: int) -> int:
    """Round ``x`` up to the nearest multiple of ``multiple``."""
    return cdiv(x, multiple) * multiple


def next_power_of_2(x: int) -> int:
    """Smallest power of two >= x (>=1)."""
    if x <= 1:
        return 1
    return 1 << (x - 1).bit_length()


def pad_to(seq, length, pad_value=0):
    """Pad a python list to ``length`` with ``pad_value`` (truncates if longer)."""
    seq = list(seq)[:length]
    return seq + [pad_value] * (length - len(seq))
