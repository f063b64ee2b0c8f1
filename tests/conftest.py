"""Shared tier-1 fixtures (chaos harness satellites, docs §9).

``seeded_fabric`` builds deterministically-seeded fabrics: the same seed
replays the same loss-mask / jitter draws, and the default link is
zero-latency so delivery is synchronous — tests drive time explicitly
(``virtual_clock`` + ``ChaosInjector.poll(now=...)``) instead of sleeping.
"""
import pytest

from repro.chaos import VirtualClock
from repro.core.fabric import Fabric, LinkModel


@pytest.fixture
def seeded_fabric():
    """Factory: ``seeded_fabric(seed=..., default_link=...) -> Fabric``.

    Zero-latency default link unless overridden, so sends deliver before
    ``send_batch`` returns and assertions never race a timer thread."""

    def make(seed: int = 0, *, default_link: LinkModel = None, **kw) -> Fabric:
        return Fabric(default_link=default_link or LinkModel(),
                      seed=seed, **kw)

    return make


@pytest.fixture
def virtual_clock():
    """A ``VirtualClock`` starting at t=0 — pass ``now=clock()`` to
    ``ChaosInjector.start/poll`` (and ``advance`` between polls) to replay a
    chaos schedule deterministically on any CI machine."""
    return VirtualClock(0.0)


@pytest.fixture(autouse=True, scope="module")
def no_inherited_mesh():
    """Each test module starts with no ambient mesh. ``compat.set_mesh`` is
    persistent by design, and the benchmark's train driver
    (``bench/drivers/train.py``) and a few modules here set one for the rest
    of their process; in a one-process run of the whole suite
    (``scripts/verify.sh``) it reached later modules' shard_maps. An
    axis-less mesh is no mesh to ``compat.current_mesh`` and to shard_map."""
    import jax
    import numpy as np

    from repro import compat

    if compat.current_mesh() is not None:
        compat.set_mesh(jax.sharding.Mesh(np.array(jax.devices()[0]), ()))
    yield
