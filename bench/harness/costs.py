"""Operations and bytes of the work a configuration defines, from shapes.

These count what the configuration states (its served dtype, its widths),
not what the program happens to move: a program that holds wider weights
than stated reads a lower roofline share, and one that meets the stated
work reads 100% at best. A model's counts are its architecture module's
(``bench/arch/<model_type>.py``); the rooflines over them and the MIPS
scan's counts are here.
"""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
               "float8": 1}


def decode_step_least_s(arch, cfg: dict, peaks: dict, live: float,
                        kv_positions: float) -> tuple:
    """Least time of one decode step on ``peaks``: the larger of its bytes
    over HBM bandwidth and its FLOPs over the peak of the stated dtype, as
    the architecture module ``arch`` counts them for ``live`` tokens with
    ``kv_positions`` cached positions summed over the live slots. Returns
    (seconds, bound)."""
    byts, flops = arch.decode_step_cost(cfg, live, kv_positions)
    t_mem = byts / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["flops_per_s"][cfg["torch_dtype"]]
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")


def scan_cost(rows: int, dim: int, q: float, k: int = 1) -> tuple:
    """(ops, bytes) of one int8 MIPS call over ``rows`` x ``dim`` int8 with
    f32 per-row scales, for ``q`` queries: the store and its scales read
    once, the int8 queries with their scales in, ``k`` (score, row) pairs
    out per query."""
    ops = 2 * q * rows * dim
    byts = rows * dim + rows * 4 + q * (dim + 4) + q * k * 8
    return ops, byts


def scan_least_s(rows: int, dim: int, q: float, peaks: dict) -> tuple:
    ops, byts = scan_cost(rows, dim, q)
    t_mem = byts / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["flops_per_s"]["int8"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
