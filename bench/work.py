"""Operations and bytes that a kernel call must do, from its shapes.

``lb_sax``: the LB_SAX (MINDIST) filter of ``queries`` query PAA rows of
``segments`` float32 values against ``rows`` iSAX codes of ``segments``
uint8 symbols each, writing one float32 bound per (query, row):

* bytes: every code read once, ``rows * segments``; every bound written
  once, ``4 * queries * rows``; every query PAA read once,
  ``4 * queries * segments``; the two breakpoint tables,
  ``2 * 4 * alphabet``;
* operations: per (query, row, segment), two subtractions, two maxima, a
  multiply and an add, 6 in all. The table lookup is a copy, no operation.

Rows and queries are the real ones: padding that a call adds is waste,
not work it must do.
"""
from __future__ import annotations

SAX_SEGMENTS = 16
SAX_ALPHABET = 256


def lb_sax_bytes(queries: int, rows: int, segments: int = SAX_SEGMENTS,
                 alphabet: int = SAX_ALPHABET) -> int:
    return (rows * segments + 4 * queries * rows + 4 * queries * segments
            + 2 * 4 * alphabet)


def lb_sax_flops(queries: int, rows: int,
                 segments: int = SAX_SEGMENTS) -> int:
    return 6 * queries * rows * segments


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it. The
    operations are held against the chip's highest peak (bf16), so the
    compute bound is the lowest it can be."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "compute")
