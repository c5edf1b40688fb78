"""`transfer_hbm_roofline`: the least time one V-cycle's restrictions and
prolongations can take on a chip, over the device time they took per
V-cycle, in percent. Source: device_trace, through the program's named
scopes (`_scoped.py`).

The work counted is the ALGORITHM's, per chip, from the configuration and
never from the lowering, so a transfer that stages, fuses or exchanges
differently does the same counted work:

* at every level k that has a transfer (every level but the coarsest),
  with n_k the points the chip owns there: the restriction reads the
  fine residual (n_k) and writes the coarse one (n_k / 8); the
  prolongation reads the coarse correction (n_k / 8) and reads and writes
  the fine iterate (2 n_k). (3 n_k + n_k / 4) x (element size) bytes;
* the operator S costs zero bytes (a constant stencil needs none).

The levels are the hierarchy's own rule (`pa.gmg_hierarchy` as the
builder calls it, with its defaults): an axis of n cells coarsens to
ceil(n / 2), and coarsening stops once a grid has at most
`COARSE_THRESHOLD` points or an axis cannot halve (ceil(n / 2) == n, or
below 3). The chip's share of a level is ceil(n / g) cells an axis of a
part grid g: the fullest chip. Bound: memory (HBM bytes per second from
`peaks.json`); at 192^3 a chip 105.2 MB, 128.4 us at 819 GB/s.

The time is the self time of the ops under `pa.gmg.restrict` or
`pa.gmg.prolong` per V-cycle (one a Krylov iteration of `pa.pcg`), mean
over the cell's devices, less the level operator's product that the
restriction opens with (its residual b - A x: the ops under
`pa.spmv_local` inside `pa.gmg.restrict`). A transfer staged as an
operator (`gmg.transfer.operator`) puts its restriction's product under
`pa.spmv_local` too, so there the share reads high by that product.
"""
import math

from benchmark.layer_metrics._scoped import scoped_ops, seconds_by

#: `pa.gmg_hierarchy`'s default `coarse_threshold`, which the builder keeps
COARSE_THRESHOLD = 1000
PHASES = ("pa.gmg.restrict", "pa.gmg.prolong")


def level_cells(cells) -> list:
    """The cells of every level that has a transfer, finest first."""
    out, nf = [], tuple(int(n) for n in cells)
    while math.prod(nf) > COARSE_THRESHOLD:
        nc = tuple((n + 1) // 2 for n in nf)
        if nc == nf or min(nc) < 3:
            break
        out.append(nf)
        nf = nc
    return out


def transfer_bytes(cells, part_grid, itemsize: int) -> float:
    """Bytes one V-cycle's transfers move on the fullest chip."""
    total = 0.0
    for nf in level_cells(cells):
        n = math.prod(-(-c // g) for c, g in zip(nf, part_grid))
        total += 3 * n + n / 4
    return total * itemsize


def key(scopes):
    """True for a transfer's op, else None."""
    if "pa.gmg.restrict" in scopes and "pa.spmv_local" in scopes:
        return None  # the residual's product
    return True if any(p in scopes for p in PHASES) else None


def reduce(run):
    if run.mix.get("preconditioner") != "gmg":
        return None
    found = scoped_ops(run)
    if found is None:
        return None
    device_ops, lo, hi = found
    secs = seconds_by(device_ops, lo, hi, key).get(True)
    vcycles = sum(int(r["info"].get("iterations", 0)) for r in run.traced_records)
    if not secs or vcycles <= 0:
        return None
    per_vcycle = secs / len(device_ops) / vcycles
    least = transfer_bytes(
        run.cfg["cells"], run.cfg["part_grid"], run.itemsize
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / per_vcycle
