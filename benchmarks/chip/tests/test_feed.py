"""The window's feed keeps at most ``ahead`` steps in flight, waits for
them in the order they were sent, and leaves none unfinished at a drain."""

import contextlib

import pytest


class _Job:
    def __init__(self):
        self.sent = []

    def train_one(self, step):
        import jax.numpy as jnp

        self.sent.append(step)
        return jnp.float32(step)


class _Run:
    @contextlib.contextmanager
    def span(self, name):
        yield


def _feed(ahead):
    import harness

    drv = harness.load_module("loops", "train_ckpt.py")
    return drv.Feed(_Job(), _Run(), ahead)


@pytest.mark.parametrize("ahead", [1, 3, 26])
def test_feed_bounds_steps_in_flight(ahead):
    feed = _feed(ahead)
    for step in range(1, 11):
        feed.step(step)
        assert len(feed.pending) <= ahead
    assert [s for s, _ in feed.done] == list(range(1, 11 - len(feed.pending)))
    feed.drain()
    assert not feed.pending
    assert [s for s, _ in feed.done] == list(range(1, 11))
    times = [t for _, t in feed.done]
    assert times == sorted(times)


def test_feed_needs_one_step_ahead():
    with pytest.raises(ValueError):
        _feed(0)
