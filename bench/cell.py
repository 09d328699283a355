"""What a configuration family hands the harness: the program's engine for
one cell, the benchmark's own generators of its weights and data, and the
plain reference to compare it with.

A family is a module ``bench/families/<family>.py`` with one function,
``build(config, traffic, chips) -> Cell``; the configuration's file names
its family.  Weights and data are made by the benchmark from the seed and
handed to the program; the reference regenerates them the same way and
takes nothing the program made."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class Cell:
    engine: Any  # the program's FederatedEngine for this cell
    fed: dict  # FedCM settings of the traffic mix, as the reference reads them
    batch_size: int
    chunk: int  # rounds per run_rounds call
    init_params: Callable  # jitted: key -> parameter tree in the program's layout
    make_data: Callable  # seed -> dict of numpy arrays: client_x, client_y
    ref_loss: Callable  # loss(params, batch, numerics) of the plain reference
    control: str  # precision of the control (bench.reference.numerics)
    work: Callable  # n_active -> bench.counts round counts
