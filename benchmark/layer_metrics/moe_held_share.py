"""Serving control: of the assignments a decode step's live rows make, the
share given to experts held on this chip, %: the rows of the grouped
product, and what ``moe_touched_share`` follows. With one of 32 chips'
share of 512 experts beside 256 identity experts and even routing: 16 / 768
= 2.1%."""

from .. import reduce
from ._moe_split import share


def read(r):
    value = share(r, "held")
    return None if value is None else reduce.checked_share(
        "moe_held_share", value)
