"""Plain reference for tpch-q6-sf10-fleet: TPC-H Q6 in int64 numpy.

Columns are integer codes: shipdate in days since 1992-01-01, discount
in hundredths, quantity, extendedprice in cents; the revenue is
sum(extendedprice * discount) in 1/10,000 dollar, exact.  `revenue` is
the query; `revenue_float32`, the control, sums in float32.  Imports
nothing of the program.
"""
from __future__ import annotations

from typing import Mapping

import numpy as np


def _keep(cols: Mapping, year: int, discount: int, quantity: int):
    """DATE <= shipdate < DATE + 1 year (DATE = January 1 of `year`),
    discount - 1 <= l_discount <= discount + 1, l_quantity < quantity."""
    epoch = np.datetime64("1992-01-01", "D")
    lo = int((np.datetime64(f"{year:04d}-01-01", "D") - epoch).astype(int))
    hi = int((np.datetime64(f"{year + 1:04d}-01-01", "D") - epoch)
             .astype(int))
    ship, disc = cols["shipdate"], cols["discount"]
    return ((ship >= lo) & (ship < hi) & (disc >= discount - 1)
            & (disc <= discount + 1) & (cols["quantity"] < quantity))


def revenue(cols: Mapping, year: int, discount: int, quantity: int) -> int:
    keep = _keep(cols, year, discount, quantity)
    price = np.asarray(cols["price"])[keep].astype(np.int64)
    return int(np.sum(price * np.asarray(cols["discount"])[keep]
                      .astype(np.int64)))


def revenue_float32(cols: Mapping, year: int, discount: int,
                    quantity: int) -> int:
    """The control: the same rows, products and sum in float32."""
    keep = _keep(cols, year, discount, quantity)
    price = np.asarray(cols["price"])[keep].astype(np.float32)
    disc = np.asarray(cols["discount"])[keep].astype(np.float32)
    return int(np.sum(price * disc, dtype=np.float32))
