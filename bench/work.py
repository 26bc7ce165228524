"""The nominal work of a search, counted from the configuration's shapes.

The count belongs to the algorithm, not to whatever implements it, so a
later change to a kernel, its tiling or its DMA is read against the same
number; its shapes come from the index configuration the cell names.  One
query of the large-batch procedure scores its seeds once and then, in each
of ``large_hops`` hops, expands one node: it reads that node's
``max_degree`` neighbour ids and occupation factors and scores each
neighbour row.  Scoring one row of ``d`` elements moves the row once and
costs one multiply-add per element.

The least time for that work on a chip is the larger of bytes over the
HBM bandwidth and operations over the peak rate; which of the two bounds
it is named beside the number.
"""
from __future__ import annotations

ID_BYTES = 4      # int32 neighbour id
LAMBDA_BYTES = 4  # int32 occupation factor of the same edge


def large_search(queries: int, cfg, *, d: int) -> dict:
    """Rows scored, bytes moved and operations of ``queries`` large-batch
    searches under the index configuration ``cfg`` (its ``large_n_seeds``,
    ``large_hops``, ``max_degree`` and row residency)."""
    if cfg.quantization != "none":
        raise ValueError(f"{cfg.quantization} residency is not counted")
    rows = queries * (cfg.large_n_seeds + cfg.large_hops * cfg.max_degree)
    edges = queries * cfg.large_hops * cfg.max_degree
    itemsize = 2 if cfg.db_bf16 else 4
    return {"rows": rows,
            "bytes": rows * d * itemsize + edges * (ID_BYTES + LAMBDA_BYTES),
            "flops": rows * 2 * d}


def least_time(work: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, bound): the least time the chip could take for ``work``,
    and whether HBM bandwidth (``"hbm"``) or compute (``"compute"``) sets
    it.  Compute is held to the bf16 peak, the fastest the MXU runs."""
    t_mem = work["bytes"] / peaks["hbm_bytes_per_s"]
    t_cmp = work["flops"] / peaks["bf16_flop_per_s"]
    return (t_mem, "hbm") if t_mem >= t_cmp else (t_cmp, "compute")
