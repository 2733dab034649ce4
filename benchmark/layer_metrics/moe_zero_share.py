"""Serving control: of the assignments a decode step's live rows make, the
share given to identity (zero-compute) experts, %: choices that cost no
weights and no product. With 256 identity outputs among 768 and even
routing: 33.3%."""

from .. import reduce
from ._moe_split import share


def read(r):
    value = share(r, "zero")
    return None if value is None else reduce.checked_share(
        "moe_zero_share", value)
