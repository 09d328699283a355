"""Fused server round-close: masked cohort mean + momentum EMA + param step."""
from repro.kernels.server_update.ops import fused_server_step
from repro.kernels.server_update.ref import server_update_ref

__all__ = ["fused_server_step", "server_update_ref"]
