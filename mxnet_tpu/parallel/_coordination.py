"""The one place this package reaches into jax's private modules.

jax 0.9.0's public ``jax.distributed`` offers ``initialize``,
``is_initialized`` and ``shutdown``, and no accessor for the coordination
service's key-value client.  The host-side collectives (parallel/dist.py)
and the membership leases (parallel/membership.py) are built on that
client, so both fetch it here and nowhere else.
"""
from __future__ import annotations


def client():
    """The coordination-service client of this process (None before
    ``jax.distributed.initialize``)."""
    from jax._src import distributed
    return distributed.global_state.client


def hosts_service() -> bool:
    """True in the process that runs the coordination service itself."""
    from jax._src import distributed
    return distributed.global_state.service is not None
