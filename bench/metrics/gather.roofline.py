"""gather.roofline: the least time of the bytes one device receives in a
selection's sample and survivor all-gathers, at the chip's published
inter-chip peak, over the device time under the program's ``gather``
scope per selection, in percent.

The bytes are counted from the algorithm's shapes, not from the calls
made.  Each of the m machines sends its packed sample (s_cap rows) and,
for each of the J threshold lanes, its packed survivors (f_cap rows),
with the paper's caps: s_cap = 3 p n/m + 16 for the Bernoulli rate
p = 4 sqrt(k/n), f_cap = 4 sqrt(n k)/m + k + 16, each at most n/m, and
J = ceil(log(2k) / log(1 + eps)) + 2.  A row is d * itemsize bytes of
features, 4 of id and 1 of validity.  One device receives the other
machines' share: (m - 1)/m * (m s_cap + J m f_cap) rows.  The least time
divides them by the chip's whole inter-chip bandwidth, 4 links times
``peaks.link_bw``, an upper bound on what one chip can receive."""

import math

from bench import trace_reduce as tr
from bench import trace_scopes as ts

#: ICI links of one v5e chip
LINKS = 4


def bytes_in(cfg: dict, m: int) -> float:
    """Bytes one of ``m`` machines receives per selection."""
    n, k, eps = cfg["n"], cfg["k"], cfg["eps"]
    n_local = -(-n // m)
    p = min(1.0, 4.0 * math.sqrt(k / n))
    s_cap = min(n_local, int(3 * (p * n_local)) + 16)
    f_cap = min(n_local, int(4 * (math.sqrt(n * k) / m)) + k + 16)
    lanes = max(4, math.ceil(math.log(max(2 * k, 4)) / math.log1p(eps)) + 2)
    itemsize = 2 if cfg["precision"] == "bf16" else 4
    rows = m * s_cap + lanes * m * f_cap
    return (m - 1) / m * rows * (cfg["d"] * itemsize + 4 + 1)


def read(ctx):
    ms = ts.scope_ms_per_selection(ctx, "gather")
    if ms is None or ctx.chips < 2:
        return None
    least, _ = tr.least_time_s(0.0, bytes_in(ctx.config, ctx.chips),
                               ctx.peaks.flops, LINKS * ctx.peaks.link_bw)
    ctx.log(f"gather.roofline: least {least * 1e3:.4f} ms a selection "
            f"(bytes-bound), under the gather scope {ms:.4f} ms")
    return tr.roofline_pct(least, ms / 1e3)
