"""Operations and bytes of one launch of the comparison eval, from shapes.

The count is of the work itself, whatever implements it (the int64
vector form today; int8 matrix planes or int32 residues later).  One
launch evaluates A atoms against t rows of U distinct columns:

  bytes  each row's c1 at 4 B per 31-bit residue (K*n*4) and c0's
         coefficient 0 (K*4), once per launch however many atoms share
         the row; the reversed comparison key once (E*K*n*4, E = K*D
         digit polynomials); each atom's trapdoor (2*K*n*4); and the
         A*t outputs at 8 B (a value of up to 62 bits)
  ops    coefficient 0 of the key product for each of the A*t lanes:
         K towers * E digit polynomials * n multiply-accumulates, each a
         31-bit residue against an 8-bit digit, which is 4 int8
         multiply-accumulates, counted as 2 operations each as the
         chip's int8 peak counts them

Least time is the larger of bytes over the memory bandwidth and ops over
the int8 peak.  Both counts are of what any implementation has to read
and do: every residue read once, at the 4 B in which the column stores
it, and every product counted at the chip's cheapest rate.  No launch
takes less, so a share of this least time cannot pass 100%.  This count
holds for the full-noise gadget key; the zero-noise paper key's eval
reads less and needs a count of its own.
"""
from __future__ import annotations

from typing import Dict


def digits_per_tower(modulus_bits: int, gadget_log_base: int) -> int:
    """D: base-2**gadget_log_base digits of a modulus_bits-bit residue."""
    return -(-modulus_bits // gadget_log_base)


def eval_launch_cost(*, n: int, towers: int, digits: int, atoms: int,
                     rows: int, columns: int = 1) -> Dict[str, float]:
    """{"bytes", "ops"} of one eval launch (see the module docstring)."""
    K, E = towers, towers * digits
    row_bytes = K * n * 4 + K * 4
    nbytes = (columns * rows * row_bytes + E * K * n * 4
              + atoms * 2 * K * n * 4 + atoms * rows * 8)
    ops = atoms * rows * K * E * n * 4 * 2
    return {"bytes": float(nbytes), "ops": float(ops)}


def least_seconds(cost: Dict[str, float], peaks: Dict[str, float]
                  ) -> Dict[str, float]:
    """The least time of `cost` on a chip with `peaks`, and its bound."""
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = cost["ops"] / peaks["int8_ops_per_s"]
    return {"seconds": max(t_bytes, t_ops),
            "bytes_s": t_bytes, "ops_s": t_ops}
