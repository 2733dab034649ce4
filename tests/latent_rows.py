"""What the latent families' tests (GLM-4.7-Flash, Xing4.0, LongCat-Flash)
ask of a paged cache's ``latent_pages``: their tiny presets share one latent,
16 compressed values and a rope key of 8."""

import numpy as np

import jax


def pad_lanes_are_zero(cache, arenas: int) -> bool:
    """Every one of the ``arenas`` latent arenas stores the tiny model's 24
    values a token in one 128-lane row, something was written, and the lanes
    past the values are zeros on every page, the trash page (physical page
    0, where dead rows and pad positions write) included."""
    rows = [np.asarray(leaf) for path, leaf in
            jax.tree_util.tree_leaves_with_path(cache)
            if getattr(path[-1], "key", "") == "latent_pages"]
    assert len(rows) == arenas and all(r.shape[-1] == 128 for r in rows)
    assert all(r[..., :24].any() for r in rows)
    return not any(r[..., 24:].any() for r in rows)
