"""The work a program needs, counted from shapes: operations and bytes.

Each reference family (``bench/references/``) counts its own: the whole
prefill and decode steps and each kernel a roofline reader reads.  What
they share is the ``Work`` they return and how it bounds time.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, peak_flops: float, peak_bytes_per_s: float) -> float:
        """The least time the chip could take: the larger bound."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_per_s)


__all__ = ["Work"]
