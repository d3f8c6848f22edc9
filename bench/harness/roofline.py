"""The rank kernel's necessary work, from the logical shapes of one call.

One ranking call scores every live query of a round against every row of
the round's deduplicated gallery and keeps each query's best ``k``.  What
any implementation has to do, whatever it is:

  operations  Q*G*D multiply-adds of the score dot products (2 operations
              each) and one eligibility lookup per (query, gallery row)
  bytes       the query and gallery embeddings, the queries' admission
              masks and segment tags and the gallery rows' tags, each read
              once, and the top-k (score, index) written once

Q is the number of live queries in the round, G the real gallery rows, not
the padded shapes the program compiles for; the one-hot admission product
and the extra precision passes the program runs today are how it does the
work, not work the algorithm needs, and are not counted.
"""
from __future__ import annotations


def rank_call(Q: int, G: int, D: int, mask_width: int, k: int,
              gallery_tags: int, dtype_bytes: int = 4) -> tuple[int, int]:
    """(operations, bytes) of one ranking call.  ``mask_width`` is the
    admission entries per query (cameras, or fused camera-tile cells) at
    one byte each; ``gallery_tags`` the int32 tags per gallery row (camera
    and segment, plus the fused cell on the tile path)."""
    ops = 2 * Q * G * D + Q * G
    nbytes = (Q * D * dtype_bytes + G * D * dtype_bytes
              + Q * mask_width + Q * 4 + G * 4 * gallery_tags
              + Q * k * 8)
    return ops, nbytes


def least_time(ops: int, nbytes: int, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
