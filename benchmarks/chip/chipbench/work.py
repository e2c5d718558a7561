"""Work counts of one factorization, from supernode extents alone.

``column_ops`` is a copy of the solver's "true" column-op count
(``repro.core.schedule.group_flop_stats``), kept here so that a later change
to the solver cannot move the yardstick; a test holds the two equal.  It
counts a supernode of width ``w`` and tail ``m`` as ``w(w+m)w + m w m``.

``flops`` is the dense floating-point work the supernode needs, two flops to
a multiply-add: ``w^3/3`` for the diagonal block's Cholesky, ``m w^2`` for
the triangular solve of the tail and ``m^2 w`` for the lower triangle of its
update matrix.  It equals ``column_ops - 2 w^3 / 3``: the column-op count
charges the diagonal block at ``w^3``.  Rooflines use ``flops``.

``supernode_bytes`` is what the fused kernel must move through HBM at the
least for one supernode: its lower-triangular panel in and factored out, and
the lower triangle of its update matrix out, at the device's value size.
Padding, bucket shapes and batching are the program's choices and are not
counted.
"""
from __future__ import annotations


def supernode_column_ops(w: int, m: int) -> int:
    return w * (w + m) * w + m * w * m


def supernode_flops(w: int, m: int) -> float:
    return w ** 3 / 3.0 + m * w * w + m * m * w


def supernode_bytes(w: int, m: int, itemsize: int) -> int:
    panel = w * (w + 1) // 2 + m * w
    return itemsize * (2 * panel + m * (m + 1) // 2)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
