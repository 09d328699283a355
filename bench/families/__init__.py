"""Configuration families: ``bench/families/<family>.py`` builds a cell of
every configuration whose file names that family."""
from __future__ import annotations

from repro.configs.base import FedConfig


def fed_config(traffic: dict) -> FedConfig:
    """The program's FedConfig from a traffic mix's ``fed`` fields."""
    return FedConfig(**traffic["fed"])
