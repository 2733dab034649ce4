"""Kernels: of the trips the latent page walk's loop made in decode steps,
the share a live row made, %: ``latent_walk_trips_live`` over
``latent_walk_trips_run``, the engine's counts over the window
(``kubeml_tpu/serving/stats.py``), all latent layers. A program row is
``ceil(depth / C)`` trips of ``C`` pages whatever the table's width, so no
trip is without pages; one that is not live is a dead row's: a program row
whose table starts at the trash page is one trip of that one page a step,
until the next admit lands. An
engine without the counters (a model that pages K/V, a commit before PR 49,
whose kernel ran a grid of table width / 8 programs a row): None."""

from ._kinds import counter_share


def read(r):
    return counter_share(r, "latent_walk_trips_live", "latent_walk_trips_run")
